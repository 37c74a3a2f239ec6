"""jaxlint tier-1 suite: per-rule fixtures, suppressions, and the ratchet.

The analyzer is pure ``ast`` (no jax import), so these tests are
millisecond-fast and run anywhere.  The final test IS the CI ratchet: it
scans the repo's real hazard surface against the committed baseline and
fails only on NEW violations — the same check
``python lint_tpu.py`` performs, wired into tier-1.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pdnlp_tpu.analysis import analyze_paths, baseline, default_paths  # noqa: E402
from pdnlp_tpu.analysis.core import all_rules  # noqa: E402

FIXTURES = os.path.join(REPO, "tests", "fixtures", "jaxlint")


def hits(name, rule_id=None):
    """(rule_id, line) findings for one fixture file."""
    path = os.path.join(FIXTURES, name)
    found = analyze_paths([path], root=REPO)
    if rule_id:
        found = [f for f in found if f.rule_id == rule_id]
    return [(f.rule_id, f.line) for f in found]


def all_hits(name):
    path = os.path.join(FIXTURES, name)
    return [(f.rule_id, f.line)
            for f in analyze_paths([path], root=REPO)]


# ------------------------------------------------------------ per-rule exact

def test_r1_host_sync_positive():
    assert all_hits("r1_pos.py") == [
        ("R1", 8), ("R1", 13), ("R1", 18), ("R1", 23)]


def test_r1_host_sync_negative():
    assert hits("r1_neg.py", "R1") == []


def test_r2_traced_branch_positive():
    assert all_hits("r2_pos.py") == [
        ("R2", 7), ("R2", 14), ("R2", 21), ("R2", 28)]


def test_r2_traced_branch_negative():
    assert hits("r2_neg.py", "R2") == []


def test_r3_key_reuse_positive():
    assert all_hits("r3_pos.py") == [("R3", 7), ("R3", 13), ("R3", 19)]


def test_r3_key_reuse_negative():
    assert hits("r3_neg.py", "R3") == []


def test_r4_unblocked_timing_positive():
    assert all_hits("r4_pos.py") == [("R4", 11), ("R4", 19)]


def test_r4_unblocked_timing_negative():
    assert hits("r4_neg.py", "R4") == []


def test_r4_tracer_span_does_not_exempt_timing():
    # an obs span around the dispatch is observability, not a barrier —
    # a manual delta inside it must still be flagged
    assert all_hits("r4_tracer_pos.py") == [("R4", 14)]


def test_r4_tracer_block_is_the_exempt_barrier():
    # Span.block wraps jax.block_until_ready — the sanctioned fix
    assert hits("r4_tracer_neg.py", "R4") == []


def test_r4_hint_names_the_tracer_block_api():
    path = os.path.join(FIXTURES, "r4_tracer_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R4"][0]
    assert "block" in f.hint and "pdnlp_tpu.obs" in f.hint


def test_r5_missing_donate_positive():
    assert all_hits("r5_pos.py") == [
        ("R5", 11), ("R5", 17), ("R5", 20), ("R5", 25)]


def test_r5_missing_donate_negative():
    assert hits("r5_neg.py", "R5") == []


def test_r6_unknown_axis_positive():
    assert all_hits("r6_pos.py") == [("R6", 4), ("R6", 5), ("R6", 12)]


def test_r6_unknown_axis_negative():
    assert hits("r6_neg.py", "R6") == []


def test_r7_put_in_step_loop_positive():
    assert all_hits("r7_pos.py") == [("R7", 7), ("R7", 13), ("R7", 21)]


def test_r7_put_in_step_loop_negative():
    assert hits("r7_neg.py", "R7") == []


def test_r7_hint_points_at_the_pipeline():
    path = os.path.join(FIXTURES, "r7_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R7"][0]
    assert "pdnlp_tpu.data.pipeline" in f.hint


def test_r8_xla_attention_positive():
    # literal impl pin (10), literal attn_impl pin (12), the legacy
    # auto-demotion IfExp (19), library XLA attention (29)
    assert all_hits("r8_pos.py") == [("R8", 10), ("R8", 12), ("R8", 19),
                                     ("R8", 29)]


def test_r8_xla_attention_negative():
    assert hits("r8_neg.py", "R8") == []


def test_r8_hint_points_at_attn_impl():
    path = os.path.join(FIXTURES, "r8_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R8"][0]
    assert "--attn_impl" in f.hint


def test_r9_blocking_ckpt_positive():
    # module-resolved save_state (8), save_params (15), the trainer-style
    # self.save_resume method call (23)
    assert all_hits("r9_pos.py") == [("R9", 8), ("R9", 15), ("R9", 23)]


def test_r9_blocking_ckpt_negative():
    assert hits("r9_neg.py", "R9") == []


def test_r9_hint_points_at_the_async_saver():
    path = os.path.join(FIXTURES, "r9_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R9"][0]
    assert "async_ckpt" in f.hint and "submit" in f.hint


def test_r10_unspanned_serve_block_positive():
    # var fetch (10), inline fetch (14), block_until_ready call (19),
    # .block_until_ready() method (25) — all on _jit_forward results
    assert all_hits("r10_pos.py") == [("R10", 10), ("R10", 14),
                                      ("R10", 19), ("R10", 25)]


def test_r10_unspanned_serve_block_negative():
    assert hits("r10_neg.py", "R10") == []


def test_r10_requires_serve_context(tmp_path):
    """Modules outside the serve surface (no pdnlp_tpu.serve import, not
    under pdnlp_tpu/serve/) are R4's territory, never R10's."""
    p = tmp_path / "plain.py"
    p.write_text("import jax\n\n"
                 "def f(jit_forward, x):\n"
                 "    out = jit_forward(x)\n"
                 "    return jax.device_get(out)\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R10"] == []


def test_r10_hint_names_the_tracer():
    path = os.path.join(FIXTURES, "r10_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R10"][0]
    assert "span" in f.hint and "pdnlp_tpu.obs" in f.hint


def test_r11_unpacked_serve_forward_positive():
    # bare dict literal (11), bare constant-tuple comprehension (21),
    # segment_ids without cls_positions (30) — each in a scope that
    # routes segmented=True
    assert all_hits("r11_pos.py") == [("R11", 11), ("R11", 21),
                                      ("R11", 30)]


def test_r11_unpacked_serve_forward_negative():
    assert hits("r11_neg.py", "R11") == []


def test_r11_requires_serve_context(tmp_path):
    """The packed-channel contract binds serve modules only — a train or
    bench scope assembling a plain batch is not in scope."""
    p = tmp_path / "plain.py"
    p.write_text(
        "from pdnlp_tpu.ops.attention import routed_impl_cached\n\n"
        "def f(jit_forward, x, seq):\n"
        "    impl = routed_impl_cached('auto', seq, segmented=True)\n"
        "    batch = {'input_ids': x, 'attention_mask': x,\n"
        "             'token_type_ids': x}\n"
        "    return jit_forward(batch), impl\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R11"] == []


def test_r11_hint_names_the_packing_surface():
    path = os.path.join(FIXTURES, "r11_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R11"][0]
    assert "cls_positions" in f.hint and "pack_id_lists" in f.hint


def test_r12_device_value_in_span_attr_positive():
    # raw device attr (7), float() sync inside the span call (14), a
    # dispatch result in a record attr (22), and the same through a
    # propagated variable (29)
    assert all_hits("r12_pos.py") == [("R12", 7), ("R12", 14),
                                      ("R12", 22), ("R12", 29)]


def test_r12_device_value_in_span_attr_negative():
    # host attrs, static .shape/len reads, the materialize-at-the-barrier
    # shape (float(jax.device_get(...)) LAUNDERS for propagation), and
    # Tracer.block's value argument
    assert hits("r12_neg.py", "R12") == []


def test_r12_requires_jax_module(tmp_path):
    """A module that never imports jax has no device values — its span
    attrs are host data by construction."""
    p = tmp_path / "hostonly.py"
    p.write_text(
        "def f(tracer, step, state, batch):\n"
        "    state, metrics = step(state, batch)\n"
        "    with tracer.span('log', loss=metrics['loss']):\n"
        "        pass\n"
        "    return state\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R12"] == []


def test_r12_hint_names_the_barrier():
    path = os.path.join(FIXTURES, "r12_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R12"][0]
    assert "device_get" in f.hint and "block" in f.hint


def test_r13_unrecorded_actuation_positive():
    # direct knob write (7), raw apply_knob (11), nested admission
    # threshold write (15), raw scale call (19), augmented write (23) —
    # each outside _actuate in a controller-scope module
    assert all_hits("r13_pos.py") == [("R13", 7), ("R13", 11),
                                      ("R13", 15), ("R13", 19),
                                      ("R13", 23)]


def test_r13_unrecorded_actuation_negative():
    assert hits("r13_neg.py", "R13") == []


def test_r13_requires_controller_context(tmp_path):
    """The router/batcher own their knobs until a controller is in play:
    a module that never imports the controller (the router itself, the
    CLI wiring) may set hedge_ms/apply_knob freely."""
    p = tmp_path / "plain.py"
    p.write_text("def build(router):\n"
                 "    router.hedge_ms = 25.0\n"
                 "    router.apply_knob('max_wait_ms', 10.0)\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R13"] == []


def test_r13_hint_names_the_choke_point():
    path = os.path.join(FIXTURES, "r13_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R13"][0]
    assert "_actuate" in f.hint and "pdnlp_tpu.obs.decision" in f.hint


def test_r14_quadratic_bias_positive():
    # segment_bias call / ID outer-product / literal [.., 512, 512]
    # buffer, each in a hot-path builder scope
    assert all_hits("r14_pos.py") == [("R14", 10), ("R14", 18),
                                      ("R14", 25)]


def test_r14_quadratic_bias_negative():
    assert hits("r14_neg.py", "R14") == []


def test_r14_sanctioned_site_exempt(tmp_path):
    """ops/attention.py's XLA fallback is the ONE sanctioned
    materialization — the rule must not flag its own escape hatch."""
    sub = tmp_path / "pdnlp_tpu" / "ops"
    sub.mkdir(parents=True)
    p = sub / "attention.py"
    p.write_text("import jax\n"
                 "from pdnlp_tpu.data.packing import segment_bias\n\n"
                 "def _forward(q, seg):\n"
                 "    return segment_bias(seg)\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R14"] == []


def test_r14_hint_names_the_routed_alternative():
    path = os.path.join(FIXTURES, "r14_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R14"][0]
    assert "segment_ids" in f.hint and "ops.attention" in f.hint


def test_r15_unrecorded_traffic_shift_positive():
    # direct canary-fraction write (7), augmented shadow-fraction write
    # (11), raw rollback drain (15), raw extract/adopt re-home (19, 20) —
    # each outside _actuate/_apply/apply_knob in a fleet-scope module
    assert all_hits("r15_pos.py") == [("R15", 7), ("R15", 11),
                                      ("R15", 15), ("R15", 19),
                                      ("R15", 20)]


def test_r15_unrecorded_traffic_shift_negative():
    assert hits("r15_neg.py", "R15") == []


def test_r15_requires_fleet_context(tmp_path):
    """The fleet module itself owns the fractions (its __init__/apply_knob
    ARE the setter surface — the R13 router precedent), and a module that
    never imports the fleet has no rollout state to shift."""
    p = tmp_path / "plain.py"
    p.write_text("def build(thing):\n"
                 "    thing.canary_fraction = 0.5\n"
                 "    thing.extract_queued()\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R15"] == []


def test_r15_fleet_module_itself_out_of_scope():
    """pdnlp_tpu/serve/fleet.py writes its own fractions in __init__ and
    apply_knob/_rollback_drain — the sanctioned setter surface."""
    path = os.path.join(REPO, "pdnlp_tpu", "serve", "fleet.py")
    assert [f for f in analyze_paths([path], root=REPO)
            if f.rule_id == "R15"] == []


def test_r15_hint_names_the_choke_point():
    path = os.path.join(FIXTURES, "r15_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R15"][0]
    assert "_actuate" in f.hint and "canary_fraction" in f.hint


def test_r16_kv_realloc_positive():
    # per-token cache concatenate rebuilds (9, 10), append-grown past
    # (18), stack rebuild (25), paged idiom: page-table rebuilt by
    # concatenate (32) and page arrays re-stacked (33) — each in a loop
    # dispatching a decode/generate-shaped call
    assert all_hits("r16_pos.py") == [("R16", 9), ("R16", 10),
                                      ("R16", 18), ("R16", 25),
                                      ("R16", 32), ("R16", 33)]


def test_r16_kv_realloc_negative():
    # .at[].set / dynamic_update_slice (the fix, slot AND paged forms),
    # one-time cache/table assembly outside decode loops, non-cache
    # concatenation in a decode loop, and cache-NAMED appends in a
    # non-decode loop all stay clean
    assert hits("r16_neg.py", "R16") == []


def test_r16_requires_decode_dispatch(tmp_path):
    """A cache concatenate in a plain data loop is not a decode-loop
    rebuild — the loop must dispatch a decode/step-shaped call."""
    p = tmp_path / "plain.py"
    p.write_text("import jax.numpy as jnp\n"
                 "def gather(batches, kv_cache):\n"
                 "    for b in batches:\n"
                 "        kv_cache = jnp.concatenate([kv_cache, b])\n"
                 "    return kv_cache\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R16"] == []


def test_r16_hint_names_the_fix():
    path = os.path.join(FIXTURES, "r16_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R16"][0]
    assert "donate" in f.hint.lower()
    assert "dynamic_update_slice" in f.hint


def test_r17_spec_retrace_positive():
    # verify window sliced to the runtime accepted length (9), draft
    # window sliced to an adaptive k (16), verify sliced to runtime
    # start:end bounds (23) — each inside a decode-shaped loop
    assert all_hits("r17_pos.py") == [("R17", 9), ("R17", 16),
                                      ("R17", 23)]


def test_r17_spec_retrace_negative():
    # full-width dispatch with the real length as masked data (the
    # engine spelling), literal-bound slices, runtime slices on
    # non-speculation calls, and variable-width verify OUTSIDE a decode
    # loop all stay clean
    assert hits("r17_neg.py", "R17") == []


def test_r17_requires_decode_loop(tmp_path):
    """A variable-width verify in a plain data loop is a one-off shape
    per call site, not a per-round retrace — the loop must dispatch a
    decode/speculation-shaped call."""
    p = tmp_path / "plain.py"
    p.write_text("import jax\n"
                 "def score(batches, verify_ids, params, kv, a):\n"
                 "    out = []\n"
                 "    for b in batches:\n"
                 "        out.append(len(b))\n"
                 "    return verify_ids(params, kv[:, : a + 1])\n")
    assert [f for f in analyze_paths([str(p)], root=str(tmp_path))
            if f.rule_id == "R17"] == []


def test_r17_hint_names_the_fix():
    path = os.path.join(FIXTURES, "r17_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R17"][0]
    assert "verify_ids" in f.hint
    assert "data argument" in f.hint


def test_r18_handoff_retrace_positive():
    # export index built from the filtered live-page list (10), import
    # target sliced to the runtime count (15), inline comprehension
    # (19), filter()-built destination (25)
    assert all_hits("r18_pos.py") == [("R18", 10), ("R18", 15),
                                      ("R18", 19), ("R18", 25)]


def test_r18_handoff_retrace_negative():
    # the engine spelling (full table row), sentinel np.full padding,
    # literal-bound slices, the runtime count as scalar data, and a
    # varlen array passed to a NON-handoff call all stay clean
    assert hits("r18_neg.py", "R18") == []


def test_r18_hint_names_the_fix():
    path = os.path.join(FIXTURES, "r18_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "R18"][0]
    assert "pages_per_stream" in f.hint
    assert "export_pages" in f.hint


# ------------------------------------------------- concurrency suite (T1-T3)

def test_t1_unguarded_attr_positive():
    # bare worker-path read (34), unlocked call to a helper that touches
    # a guarded attr (35), bare worker-path write (39)
    assert all_hits("t1_pos.py") == [("T1", 34), ("T1", 35), ("T1", 39)]


def test_t1_unguarded_attr_negative():
    # condition aliasing, entry-held helpers, init-only attrs, lifecycle
    # methods off the worker path, and lock-owning UNthreaded classes
    assert hits("t1_neg.py", "T1") == []


def test_t1_message_names_the_lock_and_attr():
    path = os.path.join(FIXTURES, "t1_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "T1"][0]
    assert "Pool._lock" in f.message and "_pending" in f.message


def test_t2_lock_order_cycle_positive():
    # ONE finding for the accounts/audit cycle, placed on the inner
    # acquisition of the first edge, citing all edges (including the
    # interprocedural one through _locked_accounts)
    got = hits("t2_pos.py", "T2")
    assert got == [("T2", 12)]
    path = os.path.join(FIXTURES, "t2_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "T2"][0]
    assert "_accounts" in f.message and "_audit" in f.message
    assert "t2_pos.py:17" in f.message  # the interprocedural call site


def test_t2_lock_order_cycle_negative():
    assert hits("t2_neg.py", "T2") == []


def test_t3_blocking_under_lock_positive():
    # queue wait (14), sleep (19), future wait (23), jit dispatch (27),
    # and file I/O reached through a helper (32, citing _write's open)
    assert all_hits("t3_pos.py") == [
        ("T3", 14), ("T3", 19), ("T3", 23), ("T3", 27), ("T3", 32)]


def test_t3_blocking_under_lock_negative():
    assert hits("t3_neg.py", "T3") == []


def test_t3_interprocedural_finding_cites_the_io_line():
    path = os.path.join(FIXTURES, "t3_pos.py")
    f = [x for x in analyze_paths([path], root=REPO)
         if x.rule_id == "T3" and x.line == 32][0]
    assert "t3_pos.py:35" in f.message and "open" in f.message


def test_concurrency_suppression_honored():
    # the commented write is silenced; the bare read right after fires
    assert hits("t_suppressed.py", "T1") == [("T1", 25)]


def test_suite_selection_partitions_rules():
    path = os.path.join(FIXTURES, "t1_pos.py")
    assert analyze_paths([path], root=REPO, suite="tracing") == []
    conc = analyze_paths([path], root=REPO, suite="concurrency")
    assert {f.rule_id for f in conc} == {"T1"}
    r1 = os.path.join(FIXTURES, "r1_pos.py")
    assert analyze_paths([r1], root=REPO, suite="concurrency") == []
    assert {f.rule_id
            for f in analyze_paths([r1], root=REPO, suite="tracing")} \
        == {"R1"}


def test_concurrency_baseline_ratchet(tmp_path):
    import shutil

    tree = tmp_path / "tree"
    tree.mkdir()
    shutil.copy(os.path.join(FIXTURES, "t3_pos.py"), tree / "old.py")
    found = analyze_paths([str(tree)], root=str(tmp_path))
    assert {f.rule_id for f in found} == {"T3"}
    base = tmp_path / "base.json"
    baseline.write(found, str(base))
    # unchanged tree: the grandfathered T findings are not new
    new, fixed = baseline.compare(
        analyze_paths([str(tree)], root=str(tmp_path)),
        baseline.load(str(base)))
    assert new == [] and fixed == 0
    # a fresh concurrency hazard IS new
    (tree / "fresh.py").write_text(
        "import threading, time\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)\n")
    new, _ = baseline.compare(
        analyze_paths([str(tree)], root=str(tmp_path)),
        baseline.load(str(base)))
    assert [(f.rule_id, f.path, f.line) for f in new] == \
        [("T3", "tree/fresh.py", 10)]


# ------------------------------------------------- interprocedural core

def test_program_info_resolves_cross_object_attr_types():
    """The `rep.hb = Heartbeat(...)` pattern: an attribute assigned
    through a typed local lands on the local's class model, so
    `rep.hb.beat(...)` resolves cross-module."""
    from pdnlp_tpu.analysis.core import ProgramInfo, parse_module
    router = os.path.join(REPO, "pdnlp_tpu", "serve", "router.py")
    watchdog = os.path.join(REPO, "pdnlp_tpu", "parallel", "watchdog.py")
    prog = ProgramInfo([
        parse_module(router, "pdnlp_tpu/serve/router.py"),
        parse_module(watchdog, "pdnlp_tpu/parallel/watchdog.py")])
    rep = prog.classes["pdnlp_tpu.serve.router._Replica"]
    assert rep.attr_types["hb"] == "pdnlp_tpu.parallel.watchdog.Heartbeat"
    rr = prog.classes["pdnlp_tpu.serve.router.ReplicaRouter"]
    assert rr.return_types["_make_replica"] \
        == "pdnlp_tpu.serve.router._Replica"


def test_concurrency_model_sees_condition_aliasing_and_threads():
    from pdnlp_tpu.analysis.core import ProgramInfo, parse_module
    from pdnlp_tpu.analysis.concurrency.model import ConcurrencyModel
    path = os.path.join(FIXTURES, "t1_pos.py")
    prog = ProgramInfo([parse_module(path, "t1_pos.py")])
    model = ConcurrencyModel(prog)
    groups = model.lock_groups("t1_pos.Pool")
    assert groups["_cond"] == "_lock"  # Condition(self._lock) aliases
    assert model.class_is_threaded("t1_pos.Pool")
    assert "m:t1_pos.Pool._run" in model.thread_reachable
    assert "m:t1_pos.Pool._drain" in model.thread_reachable  # closure
    assert "m:t1_pos.Pool.submit" not in model.thread_reachable


def test_entry_held_infers_helper_lock_context():
    from pdnlp_tpu.analysis.core import ProgramInfo, parse_module
    from pdnlp_tpu.analysis.concurrency.model import ConcurrencyModel
    path = os.path.join(FIXTURES, "t1_neg.py")
    prog = ProgramInfo([parse_module(path, "t1_neg.py")])
    model = ConcurrencyModel(prog)
    entry = model.entry_held("t1_neg.WellLocked")
    assert entry["_pop_locked"] == \
        frozenset({("C", "t1_neg.WellLocked", "_lock")})
    assert entry["_run"] == frozenset()


def test_repo_serve_surface_concurrency_clean():
    """The triage pin: the serving stack and the async checkpointer run
    clean on the concurrency suite (every real finding in this tree was
    fixed or suppressed-with-reason in place; a reintroduction is a NEW
    finding and fails the surface ratchet below)."""
    paths = [os.path.join(REPO, "pdnlp_tpu", "serve"),
             os.path.join(REPO, "pdnlp_tpu", "parallel", "watchdog.py"),
             os.path.join(REPO, "pdnlp_tpu", "train", "async_ckpt.py")]
    found = analyze_paths(paths, root=REPO, suite="concurrency")
    assert found == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule_id} {f.message}" for f in found)


# ------------------------------------------------------------------- sarif

def test_sarif_round_trips_a_mixed_report(tmp_path):
    """--format sarif on a tree with tracing AND concurrency findings:
    the SARIF results map 1:1 back onto analyze_paths' findings (rule,
    file, 1-indexed line/col), and rule metadata rides along."""
    import shutil

    tree = tmp_path / "t"
    tree.mkdir()
    shutil.copy(os.path.join(FIXTURES, "r1_pos.py"), tree / "a.py")
    shutil.copy(os.path.join(FIXTURES, "t3_pos.py"), tree / "b.py")
    env = {**os.environ, "PYTHONPATH": REPO}
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "lint_tpu.py"),
         "--format", "sarif", "--no-baseline", str(tree)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert out.returncode == 1  # findings exist and count as new
    sarif = json.loads(out.stdout)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "jaxlint"
    got = {(res["ruleId"],
            res["locations"][0]["physicalLocation"]["artifactLocation"]["uri"],
            res["locations"][0]["physicalLocation"]["region"]["startLine"],
            res["locations"][0]["physicalLocation"]["region"]["startColumn"])
           for res in run["results"]}
    want = {(f.rule_id, f.path, f.line, f.col + 1)
            for f in analyze_paths([str(tree)], root=str(tmp_path))}
    assert got == want
    # every referenced rule is declared with its fix hint
    declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {res["ruleId"] for res in run["results"]} <= declared
    assert all(res["level"] == "error" for res in run["results"])
    assert all(res["properties"]["hint"] for res in run["results"])


def test_sarif_baseline_marks_grandfathered_as_notes(tmp_path):
    import shutil

    tree = tmp_path / "t"
    tree.mkdir()
    shutil.copy(os.path.join(FIXTURES, "t3_pos.py"), tree / "b.py")
    env = {**os.environ, "PYTHONPATH": REPO}
    base = tmp_path / "base.json"
    subprocess.run(
        [sys.executable, os.path.join(REPO, "lint_tpu.py"),
         "--write-baseline", "--baseline", str(base), str(tree)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "lint_tpu.py"),
         "--format", "sarif", "--baseline", str(base), str(tree)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert out.returncode == 0  # nothing new vs baseline
    sarif = json.loads(out.stdout)
    results = sarif["runs"][0]["results"]
    assert results and all(r["level"] == "note" for r in results)


def test_partial_suite_scopes_the_baseline(tmp_path):
    """--suite concurrency must not count the unscanned tracing debt as
    'fixed', and --write-baseline refuses under a partial scan — a
    suite-filtered baseline would silently drop the other suite's
    grandfathered findings."""
    import shutil

    tree = tmp_path / "t"
    tree.mkdir()
    shutil.copy(os.path.join(FIXTURES, "r1_pos.py"), tree / "a.py")
    shutil.copy(os.path.join(FIXTURES, "t3_pos.py"), tree / "b.py")
    env = {**os.environ, "PYTHONPATH": REPO}
    base = tmp_path / "base.json"

    def run(*extra):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "lint_tpu.py"),
             "--baseline", str(base), *extra, str(tree)],
            capture_output=True, text=True, env=env, cwd=str(tmp_path))

    assert run("--write-baseline").returncode == 0
    out = run("--suite", "concurrency", "--json")
    assert out.returncode == 0
    report = json.loads(out.stdout)
    assert report["summary"]["new"] == 0
    assert report["summary"]["fixed_vs_baseline"] == 0  # R debt ≠ fixed
    refused = run("--suite", "concurrency", "--write-baseline")
    assert refused.returncode == 2
    assert "refusing" in refused.stderr


def test_the_gate_passes_on_the_real_tree_and_fails_without_its_baseline(
        monkeypatch, capsys):
    """``lint_tpu.py`` (what ``scripts/lint_gate.sh`` runs) on the real tree
    against the committed baseline: exit 0, nothing new and nothing FIXED
    (the baseline holds no entry for a file that is gone).  With the
    baseline emptied out every grandfathered finding reads as new and the
    gate exits 1."""
    from pdnlp_tpu.analysis import baseline as baseline_mod
    from pdnlp_tpu.analysis.cli import main as lint_main

    monkeypatch.chdir(REPO)
    assert lint_main(["--json"]) == 0
    summary = json.loads(capsys.readouterr().out)["summary"]
    assert summary["new"] == 0 and summary["fixed_vs_baseline"] == 0
    for e in baseline_mod.load(baseline_mod.DEFAULT_BASELINE):
        assert os.path.exists(os.path.join(REPO, e["file"])), e["file"]

    monkeypatch.setattr(baseline_mod, "load", lambda path: [])
    assert lint_main(["--json"]) == 1
    assert json.loads(capsys.readouterr().out)["summary"]["new"] >= 1


def test_findings_carry_exact_location_and_hint():
    path = os.path.join(FIXTURES, "r1_pos.py")
    f = analyze_paths([path], root=REPO)[0]
    assert f.path.endswith("tests/fixtures/jaxlint/r1_pos.py")
    assert f.location == f"{f.path}:8"
    assert f.hint  # every finding ships a rewrite suggestion


def test_rule_registry_complete():
    # the registry sorts by id STRING (the lifecycle suite's L1-L4
    # before the R's; R10..R18 between R1 and R2; the concurrency
    # suite's T1-T3 after the R's)
    assert list(all_rules()) == ["L1", "L2", "L3", "L4",
                                 "R1", "R10", "R11", "R12", "R13", "R14",
                                 "R15", "R16", "R17", "R18", "R2", "R3",
                                 "R4", "R5", "R6", "R7", "R8", "R9",
                                 "T1", "T2", "T3"]
    suites = {rid: r.suite for rid, r in all_rules().items()}
    assert all(s == "concurrency" for rid, s in suites.items()
               if rid.startswith("T"))
    assert all(s == "tracing" for rid, s in suites.items()
               if rid.startswith("R"))
    assert all(s == "lifecycle" for rid, s in suites.items()
               if rid.startswith("L"))


# -------------------------------------------------------------- suppressions

def test_inline_suppression_honored():
    got = all_hits("suppressed.py")
    # lines 7 (same-line), 12-13 (comment-line), 23 (disable=all) silenced;
    # line 18 carries a WRONG rule id and must still fire
    assert got == [("R1", 18)]


# ------------------------------------------------------------------- ratchet

def test_baseline_ratchet_flags_only_new(tmp_path):
    import shutil

    tree = tmp_path / "tree"
    tree.mkdir()
    shutil.copy(os.path.join(FIXTURES, "r3_pos.py"), tree / "old.py")
    found = analyze_paths([str(tree)], root=str(tmp_path))
    base = tmp_path / "base.json"
    baseline.write(found, str(base))

    # unchanged tree: nothing new
    new, fixed = baseline.compare(
        analyze_paths([str(tree)], root=str(tmp_path)),
        baseline.load(str(base)))
    assert new == [] and fixed == 0

    # seed a fresh hazard: exactly it is new
    (tree / "fresh.py").write_text(
        "import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x.sum())\n")
    new, fixed = baseline.compare(
        analyze_paths([str(tree)], root=str(tmp_path)),
        baseline.load(str(base)))
    assert [(f.rule_id, f.path, f.line) for f in new] == \
        [("R1", "tree/fresh.py", 5)]

    # fix an old one: allowed (ratchet only tightens), reported as fixed
    (tree / "old.py").write_text("x = 1\n")
    (tree / "fresh.py").unlink()
    new, fixed = baseline.compare(
        analyze_paths([str(tree)], root=str(tmp_path)),
        baseline.load(str(base)))
    assert new == [] and fixed == 3


def test_baseline_survives_line_shift(tmp_path):
    src = ("import jax\n\n\n"
           "def double(key):\n"
           "    a = jax.random.normal(key, (2,))\n"
           "    b = jax.random.normal(key, (2,))\n"
           "    return a + b\n")
    f = tmp_path / "mod.py"
    f.write_text(src)
    base = tmp_path / "b.json"
    baseline.write(analyze_paths([str(f)], root=str(tmp_path)), str(base))
    # prepend lines: same violation, shifted — count ratchet stays quiet
    f.write_text("# a new comment\n# another\n" + src)
    new, _ = baseline.compare(analyze_paths([str(f)], root=str(tmp_path)),
                              baseline.load(str(base)))
    assert new == []


def test_cli_exit_codes(tmp_path):
    """End-to-end through the real CLI: clean vs seeded-hazard trees."""
    tree = tmp_path / "t"
    tree.mkdir()
    (tree / "ok.py").write_text("x = 1\n")
    env = {**os.environ, "PYTHONPATH": REPO}

    def run(*extra):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "lint_tpu.py"),
             "--json", "--no-baseline", *extra, str(tree)],
            capture_output=True, text=True, env=env, cwd=str(tmp_path))

    assert run().returncode == 0
    (tree / "bad.py").write_text(
        "import time, jax\n"
        "def go(step, s, b):\n"
        "    t0 = time.time()\n"
        "    s, _ = step(s, b)\n"
        "    return time.time() - t0\n")
    out = run()
    assert out.returncode == 1
    report = json.loads(out.stdout)
    assert [(f["rule"], f["line"]) for f in report["new_findings"]] == \
        [("R4", 5)]


def test_repo_surface_has_no_new_violations():
    """THE ratchet: the committed baseline covers the current tree."""
    base_path = os.path.join(REPO, "results", "jaxlint_baseline.json")
    assert os.path.exists(base_path), (
        "baseline missing — regenerate with `python lint_tpu.py "
        "--write-baseline`")
    findings = analyze_paths(default_paths(REPO), root=REPO)
    new, _fixed = baseline.compare(findings, baseline.load(base_path))
    assert new == [], (
        "NEW jaxlint violations (fix them or, if truly intended, add an "
        "inline `# jaxlint: disable=<id>` with a reason):\n" + "\n".join(
            f"  {f.path}:{f.line}: {f.rule_id} {f.message}" for f in new))


def test_repo_baseline_records_real_pre_existing_violations():
    """The rules bite on real code, not just fixtures: the committed
    baseline carries the tree's actual pre-existing debt (unsuppressed)."""
    base_path = os.path.join(REPO, "results", "jaxlint_baseline.json")
    entries = baseline.load(base_path)
    assert len(entries) >= 1
    assert all(e["file"] and e["line"] > 0 and e["rule"] for e in entries)
