#!/usr/bin/env python
"""Long-lived inference server over a trained checkpoint.

Turns a strategy checkpoint into a serving engine (``pdnlp_tpu.serve``):
dynamic micro-batching, sequence-length bucketing, a compiled-forward cache
that never retraces in steady state, and a JSON metrics snapshot on exit.

Interactive (default): reads one UTF-8 text per line on stdin, prints
``<label_id>\t<label>`` per line — the long-lived process a traffic frontend
would own.  Offline: ``--input FILE`` scores a whole file at maximum
throughput and writes predictions to ``--output`` (or stdout).

``--replicas N`` (N > 1) serves through the fault-tolerant
:class:`~pdnlp_tpu.serve.router.ReplicaRouter`: N engine replicas — one per
device group when enough devices exist, independent single-device engines
otherwise — behind tiered admission control (backpressure -> shed ->
reject), least-loaded dispatch, heartbeat health ejection with requeue, and
warmup-gated reintegration.  ``--replicas 1`` (default) is the original
single-engine ``DynamicBatcher`` path, byte-for-byte.

Graceful shutdown: SIGTERM/SIGINT stop intake, drain the in-flight window
(every accepted request is completed or deadline-failed — never silently
dropped), and flush the metrics snapshot + trace span files before exit.

    # online: serve stdin lines through the batcher
    python serve_tpu.py --checkpoint output/dp-cls.msgpack

    # online, 4 fault-tolerant replicas with 200ms deadlines
    python serve_tpu.py --checkpoint output/dp-cls.msgpack \
        --replicas 4 --deadline_ms 200

    # offline: score a file, dump metrics
    python serve_tpu.py --checkpoint output/dp-cls.msgpack \
        --input texts.txt --output preds.tsv --metrics_path output/serve.json

``--serve_pack auto|on|off`` picks packed online batching: admitted
requests bin-pack many-per-row into fixed ``[rows, pack_width]`` batches
(the training packer's segment channels served online), so throughput
scales with tokens, not requests; flush policy and queue admission move to
token units.  ``auto`` (default) packs where the segment-native pallas
kernel routes (TPU); ``off`` keeps the per-bucket padded path.

Live telemetry: ``--metrics_port 9100`` (an ``Args`` field) serves
Prometheus ``/metrics`` + JSON ``/healthz`` off the hot path and appends
bounded flight-recorder snapshots (``--flight_recorder`` overrides the
path) so a SIGKILL'd server still leaves evidence; ``--trace true``
additionally records spans AND per-request hop chains (every request's
admission → queue → dispatch → completion life is reconstructable by
``trace_tpu.py request <id>``).

``--fleet "id=checkpoint:dtype:replicas[:role]"`` (comma-separated; roles
``primary``/``candidate``/``cheap``) serves a **multi-model fleet**
(:class:`~pdnlp_tpu.serve.fleet.FleetRouter`): one replica pool per model
id behind one front door, with ``--shadow_fraction`` duplicating a
sampled fraction of primary traffic onto the candidate (callers always
get the primary's answer; argmax parity + latency deltas accumulate for
the rollout law), ``--canary_fraction`` routing real traffic to the
candidate, and a degrade admission band (``--degrade_at``, defaulted
between backpressure and shed when a cheap model exists) re-routing
overload to the cheap model instead of shedding it.  With ``--controller
on`` and a candidate, the rollout law steps the canary fraction up while
parity and p99 hold and auto-rolls it back (draining the candidate's
queue to the primary) when either regresses (``--rollout off`` disables
just the rollout law).

``--controller on`` (with ``--replicas N`` or ``--fleet``) attaches the
feedback control plane (:class:`~pdnlp_tpu.serve.controller.ServeController`): replica
count (warm-standby scaling, never below ``--min_replicas``),
``hedge_ms``, flush age and admission thresholds track the live telemetry
through a decision-recording, auto-reverting actuation path — controller
state rides ``/metrics`` and is summarized in ``/healthz``, and every
knob turn is reconstructable via ``trace_tpu.py decisions``.

``--decode`` serves **generative decoding** instead of classification
(:mod:`pdnlp_tpu.serve.decode`): one prompt per stdin line, tokens
STREAMED back as they decode (``<line>\\ttok\\t<piece>`` per token, a
closing ``<line>\\tgen\\t<text>``).  Each replica owns a preallocated
slot-indexed KV cache (``--decode_slots`` × ``--decode_max_len``
positions, ``--kv_dtype fp32|bf16|int8`` — int8 rides calibrated
per-channel scale tables, ``scripts/quantize_ckpt.py --kv_calib``),
bucketed prefill + one fixed-shape decode step (retrace-free after
warmup), and continuous batching: streams claim freed slots between
steps.  ``--kv_hbm_mb`` declares a KV budget (loud refusal at admission,
never an OOM); ``--replicas N`` decodes behind a
:class:`~pdnlp_tpu.serve.decode.DecodeRouter` whose kill-recovery
re-prefills orphan streams on survivors with no duplicated or lost
tokens.  ``--max_new_tokens`` bounds each stream's generation.
``--model`` picks the family (``models.families``; no other flag does):
the BERT causal LM (``bert-*``: twin K/V pools), the latent-attention,
sparse-expert decoder (``ax-k1-*``: one latent pool; ``xing4-*``: the same
with a four-stream residual mixed by hyper-connections), the hybrid
(``solar-open2-*``: gated delta-rule linear attention with a per-slot
recurrent state beside paged GQA layers, sparse experts; it shares no
prefix and refuses ``--kv_dtype int8``, ``--serve_dtype int8``,
``--speculate`` and ``--disagg`` at construction, as the latent one does).

``--speculate id=ckpt[:dtype]`` (or a bare checkpoint path) adds
**speculative decoding** to ``--decode``: a cheap drafter engine rides
each primary replica, drafts ``--draft_k`` tokens per round through its
own paged KV cache, and the primary verifies all k+1 positions in ONE
prefill-shaped call — greedy verification makes the output BITWISE
identical to primary-only decode, only faster.  The live acceptance rate
rides ``/metrics`` (per-model labels), ``/healthz`` and the snapshot;
with ``--controller on`` the speculation law adapts ``draft_k`` to it
(and switches a wasteful drafter off) through the decision-recorded
actuation path.

Serve-local flags (not ``Args`` fields): ``--checkpoint`` (default: newest
under ``--output_dir``), ``--buckets 32,64,128``, ``--max_batch_size``,
``--max_wait_ms``, ``--max_queue``, ``--deadline_ms``, ``--replicas``,
``--hedge_ms``, ``--replica_stall_s``, ``--serve_pack``, ``--controller``,
``--min_replicas``, ``--fleet``, ``--shadow_fraction``,
``--canary_fraction``, ``--degrade_at``, ``--rollout``, ``--decode``,
``--speculate``, ``--draft_k``, ``--input``,
``--output``, ``--metrics_path``, ``--no_mesh``.  Everything else (model, dtype, vocab, output_dir, ...) is
the standard ``Args`` CLI (the decode knobs — ``--decode_slots``,
``--decode_max_len``, ``--max_new_tokens``, ``--kv_dtype``,
``--kv_hbm_mb`` — are ``Args`` fields).
"""
from __future__ import annotations

import signal
import sys
from typing import Optional

from pdnlp_tpu.serve import (
    DEFAULT_BUCKETS, DynamicBatcher, InferenceEngine, ReplicaRouter,
)
from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag
from pdnlp_tpu.utils.logging import rank0_print


def build_engine(args: Args, *, checkpoint: Optional[str] = None,
                 use_mesh: bool = True) -> InferenceEngine:
    """Engine over the standard mesh (or plain jit), checkpoint loaded.

    ``checkpoint=None`` picks the newest ``.msgpack`` under
    ``args.output_dir``; an engine with NO checkpoint (fresh init weights)
    is only useful for smoke tests, so a missing checkpoint warns loudly.
    """
    mesh = None
    if use_mesh:
        from pdnlp_tpu.parallel import make_mesh

        mesh = make_mesh(num_devices=args.num_devices, shape=args.mesh_shape)
    engine = InferenceEngine(args, mesh=mesh)
    if checkpoint is None:
        checkpoint = _latest_checkpoint(args)
    if checkpoint:
        engine.load_checkpoint(checkpoint)
        rank0_print(f"serving {checkpoint}", file=sys.stderr)
    else:
        rank0_print("WARNING: no checkpoint found — serving untrained "
                    "init weights (smoke mode)", file=sys.stderr)
    return engine


def _latest_checkpoint(args: Args) -> Optional[str]:
    from pdnlp_tpu.train import checkpoint as ckpt

    return ckpt.latest(args.output_dir)


def replica_meshes(args: Args, replicas: int, use_mesh: bool) -> list:
    """One private mesh slice per replica: the devices split into
    ``replicas`` contiguous groups, so each engine owns its own device
    stream and one wedged replica cannot stall the others.

    When that cannot be done (``--no_mesh``, or fewer devices than
    replicas — the CPU tests) every entry is ``None`` and each engine is a
    plain-jit engine on the DEFAULT device: N replicas then share one chip,
    which is said on stderr rather than left to be found in a profile."""
    if use_mesh:
        import jax

        from pdnlp_tpu.parallel import make_mesh

        devices = list(jax.devices())
        if args.num_devices:
            devices = devices[: args.num_devices]
        per = len(devices) // replicas
        if per >= 1:
            return [make_mesh(devices=devices[i * per:(i + 1) * per])
                    for i in range(replicas)]
        why = f"{len(devices)} device(s) for {replicas} replicas"
    else:
        why = "--no_mesh"
    if replicas > 1:
        rank0_print(f"serve_tpu: {why} — all {replicas} engines are "
                    "plain-jit engines on the default device "
                    "(no per-replica placement)", file=sys.stderr)
    return [None] * replicas


def build_router(args: Args, replicas: int, *,
                 checkpoint: Optional[str] = None, use_mesh: bool = True,
                 buckets=DEFAULT_BUCKETS, max_batch_size: int = 8,
                 max_wait_ms: float = 5.0, max_queue: int = 256,
                 deadline_ms: Optional[float] = None,
                 hedge_ms: Optional[float] = None,
                 stall_timeout: float = 10.0,
                 serve_pack: str = "auto") -> ReplicaRouter:
    """N replica engines behind the fault-tolerant router.

    Placement is :func:`replica_meshes` (a private mesh slice each where
    the devices allow).  The same factory rebuilds an ejected replica's
    engine on :meth:`ReplicaRouter.relaunch`.
    """
    groups = replica_meshes(args, replicas, use_mesh)

    # ONE tokenizer for the whole pool: each engine would otherwise
    # re-read the vocab at construction — and again on every relaunch,
    # inflating the recovery path for no reason
    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, get_or_build_vocab

    tok = WordPieceTokenizer(get_or_build_vocab(args))

    def factory(index: int) -> InferenceEngine:
        return InferenceEngine(args, tokenizer=tok, mesh=groups[index])

    if checkpoint is None:
        checkpoint = _latest_checkpoint(args)
    engines = [factory(i) for i in range(replicas)]
    if checkpoint:
        rank0_print(f"serving {checkpoint} on {replicas} replicas",
                    file=sys.stderr)
    else:
        rank0_print("WARNING: no checkpoint found — serving untrained "
                    "init weights (smoke mode)", file=sys.stderr)
    return ReplicaRouter(
        engines, engine_factory=factory, buckets=buckets,
        max_batch_size=max_batch_size, max_wait_ms=max_wait_ms,
        max_queue=max_queue, default_deadline_ms=deadline_ms,
        hedge_ms=hedge_ms, stall_timeout=stall_timeout,
        serve_pack=serve_pack,
        pack_max_segments=getattr(args, "pack_max_segments", 16),
        checkpoint_path=checkpoint, tracer=engines[0].tracer)


def build_fleet(args: Args, specs, *, use_mesh: bool = True,
                buckets=DEFAULT_BUCKETS, max_batch_size: int = 8,
                max_wait_ms: float = 5.0, max_queue: int = 256,
                deadline_ms: Optional[float] = None,
                hedge_ms: Optional[float] = None,
                stall_timeout: float = 10.0, serve_pack: str = "auto",
                shadow_fraction: float = 0.0,
                canary_fraction: float = 0.0,
                degrade_at: Optional[int] = None):
    """A multi-model fleet from ``--fleet`` :class:`ModelSpec` rows: one
    :class:`ReplicaRouter` per model id (each spec's checkpoint/dtype/
    replica count), composed by a :class:`FleetRouter` front door.

    Placement is :func:`replica_meshes` over the fleet's TOTAL replica
    count.  The primary pool gets the degrade band (``degrade_at``,
    defaulting to 5/8 of ``max_queue`` — between the backpressure and
    shed defaults) only when a cheap model exists to absorb it."""
    import dataclasses

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, get_or_build_vocab
    from pdnlp_tpu.serve import FleetRouter, ReplicaRouter

    tok = WordPieceTokenizer(get_or_build_vocab(args))
    slices = replica_meshes(args, sum(s.replicas for s in specs), use_mesh)

    roles = {s.role: s.model_id for s in specs}
    if degrade_at is None and "cheap" in roles:
        degrade_at = (max_queue * 5) // 8
    groups = {}
    tracer = None
    offset = 0
    for spec in specs:
        # each model serves at ITS declared precision — one Args copy per
        # spec so the engines' serve_dtype (and the int8 quantized
        # template) follow the fleet spec, not the global flag
        sargs = dataclasses.replace(args, serve_dtype=spec.dtype)

        def factory(index: int, _off=offset, _sargs=sargs):
            return InferenceEngine(_sargs, tokenizer=tok,
                                   mesh=slices[_off + index])

        engines = [factory(i) for i in range(spec.replicas)]
        tracer = tracer if tracer is not None else engines[0].tracer
        rank0_print(f"fleet[{spec.model_id}] ({spec.role}): "
                    f"{spec.replicas} replica(s) of "
                    f"{spec.checkpoint or '<init weights>'} "
                    f"[{spec.dtype}]", file=sys.stderr)
        groups[spec.model_id] = ReplicaRouter(
            engines, engine_factory=factory, buckets=buckets,
            max_batch_size=max_batch_size, max_wait_ms=max_wait_ms,
            max_queue=max_queue, default_deadline_ms=deadline_ms,
            hedge_ms=hedge_ms, stall_timeout=stall_timeout,
            serve_pack=serve_pack,
            degrade_at=degrade_at if spec.role == "primary" else None,
            pack_max_segments=getattr(args, "pack_max_segments", 16),
            checkpoint_path=spec.checkpoint, model_id=spec.model_id,
            tracer=tracer)
        offset += spec.replicas
    return FleetRouter(groups, primary=roles["primary"],
                       candidate=roles.get("candidate"),
                       cheap=roles.get("cheap"),
                       shadow_fraction=shadow_fraction,
                       canary_fraction=canary_fraction, tracer=tracer)


def build_decode_pool(args: Args, replicas: int, *,
                      checkpoint: Optional[str] = None,
                      use_mesh: bool = True, buckets=DEFAULT_BUCKETS,
                      max_waiting: int = 256,
                      speculate: Optional[str] = None, draft_k: int = 4,
                      disagg: str = "off", prefill_engines: int = 1):
    """Generative serving pool: ``replicas``
    :class:`PagedDecodeEngine`\\ s — placed by :func:`replica_meshes` —
    behind a :class:`DecodeRouter` (1 replica included: the router is the
    one submit/kill/snapshot surface either way).  Each engine holds a
    refcounted page pool with cross-request prefix sharing
    (``--decode_slots`` batch rows, ``--decode_max_len`` positions a
    stream, ``--kv_dtype`` precision, pages capped by ``--kv_hbm_mb``).

    ``speculate`` (``--speculate id=ckpt[:dtype]`` or a bare checkpoint
    path) pairs every primary replica with a drafter engine built from
    the cheap model's spec: draft-``draft_k`` / verify-1 speculative
    decoding at bitwise greedy parity.  The drafter is always a
    :class:`PagedDecodeEngine` with ``prefix_share=False`` (its cold
    re-prefill rewrites pages in place — shared prefix pages would be
    corrupted) and mirrors the primary's slots/max_len geometry so slot
    indices line up pair-wise.

    ``disagg`` (``--disagg local|socket``) splits the fleet into
    prefill-role and decode-role engine pools behind a
    :class:`~pdnlp_tpu.serve.decode.DisaggDecodeRouter`: prefill engines
    run only prompt forwards and hand each stream's KV pages to a decode
    engine (``local`` = in-process payload, ``socket`` = the
    length-prefixed loopback RPC framing); ``prefill_engines`` sets the
    initial split (the controller's ``prefill_share`` knob re-balances
    it live)."""
    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, get_or_build_vocab
    from pdnlp_tpu.serve import DecodeRouter, PagedDecodeEngine
    from pdnlp_tpu.serve.decode import DisaggDecodeRouter

    groups = replica_meshes(args, replicas, use_mesh)
    tok = WordPieceTokenizer(get_or_build_vocab(args))
    if disagg != "off":
        if speculate:
            sys.exit("serve_tpu: --disagg and --speculate are exclusive "
                     "for now — decode-role engines run without "
                     "drafters")
        if replicas < 2:
            sys.exit("serve_tpu: --disagg needs --replicas >= 2 (at "
                     "least one engine per role)")
    engines = [PagedDecodeEngine(args, tokenizer=tok, mesh=groups[i],
                                 buckets=buckets) for i in range(replicas)]
    tracer = engines[0].tracer
    for e in engines[1:]:
        e.tracer = tracer  # one span/hop stream for the whole pool
    if checkpoint is None:
        checkpoint = _latest_checkpoint(args)
    if checkpoint:
        for e in engines:
            e.load_checkpoint(checkpoint)
        rank0_print(f"decoding from {checkpoint} on {replicas} "
                    "replica(s)", file=sys.stderr)
    else:
        rank0_print("WARNING: no checkpoint found — decoding from "
                    "untrained init weights (smoke mode)", file=sys.stderr)
    drafters = None
    if speculate:
        import dataclasses

        from pdnlp_tpu.serve import parse_speculate_spec

        dspec = parse_speculate_spec(speculate)
        # the drafter serves its own architecture/precision — one Args
        # copy per spec, exactly the fleet's per-model pattern; the
        # bare-checkpoint form inherits the primary's architecture (a
        # distilled same-shape checkpoint)
        dargs = args
        if "=" in speculate:
            dargs = dataclasses.replace(args, model=dspec.model_id)
        if dspec.dtype != "auto":
            dargs = dataclasses.replace(dargs, serve_dtype=dspec.dtype)
        drafters = [PagedDecodeEngine(
            dargs, tokenizer=tok, mesh=groups[i], buckets=buckets,
            tracer=tracer, slots=engines[i].slots,
            max_len=engines[i].max_len, prefix_share=False)
            for i in range(replicas)]
        if dspec.checkpoint:
            for d in drafters:
                d.load_checkpoint(dspec.checkpoint)
        rank0_print(f"speculating: drafter {dspec.model_id} "
                    f"({dspec.checkpoint or '<init weights>'} "
                    f"[{dspec.dtype}]) drafts k={draft_k} per round",
                    file=sys.stderr)
    if disagg != "off":
        transport = "socket" if disagg == "socket" else "local"
        rank0_print(f"disaggregated pools: {prefill_engines} prefill / "
                    f"{replicas - prefill_engines} decode engine(s), "
                    f"{transport} handoff", file=sys.stderr)
        return DisaggDecodeRouter(
            engines, prefill_engines=prefill_engines,
            max_waiting=max_waiting,
            default_max_new=args.max_new_tokens, transport=transport)
    return DecodeRouter(engines, max_waiting=max_waiting,
                        default_max_new=args.max_new_tokens,
                        drafters=drafters, draft_k=draft_k)


def serve_decode(args: Args, argv_flags: dict) -> None:
    """The ``--decode`` online loop: one prompt per stdin line, tokens
    STREAMED to stdout as they are generated.

    Output protocol (line-oriented, ``<line#>\\t<kind>\\t<payload>``):
    ``tok`` lines carry each token's text the moment it decodes, ``gen``
    closes the stream with the full generation, ``ERROR`` reports a
    refusal (queue/KV budget) without killing the server.  Results drain
    in submission order; a window of in-flight streams keeps the decode
    slots full (continuous batching needs waiting streams to claim freed
    slots)."""
    from collections import deque

    from pdnlp_tpu.serve.decode import detokenize

    pool = build_decode_pool(
        args, argv_flags["replicas"],
        checkpoint=argv_flags["checkpoint"],
        use_mesh=argv_flags["use_mesh"], buckets=argv_flags["buckets"],
        max_waiting=argv_flags["max_queue"],
        speculate=argv_flags.get("speculate"),
        draft_k=argv_flags.get("draft_k", 4),
        disagg=argv_flags.get("disagg", "off"),
        prefill_engines=argv_flags.get("prefill_engines", 1))
    engine = pool.engine(0)
    pool.start()
    pool.warmup()
    rank0_print("ready — one prompt per line on stdin (EOF to exit); "
                "tokens stream as `<line>\\ttok\\t<piece>`",
                file=sys.stderr)

    # the decode control plane: with --controller on, the speculation law
    # adapts draft_k to the live acceptance rate (and switches a wasteful
    # drafter off) through the same decision-recorded _actuate path the
    # classification pool's knobs ride
    controller = None
    if argv_flags.get("controller", "off") not in ("off", "false", "0",
                                                   None):
        from pdnlp_tpu.serve.controller import ServeController

        controller = ServeController(pool, tracer=engine.tracer)
        controller.start()
        rank0_print("[controller] decode control plane on (speculation "
                    "law adapts draft_k; trace_tpu.py decisions)",
                    file=sys.stderr)

    exporter = None
    if args.metrics_port or args.flight_recorder:
        from pdnlp_tpu.obs import memory_snapshot
        from pdnlp_tpu.obs.exporter import build_from_args

        sources = {"decode": pool.snapshot, "memory": memory_snapshot}
        # acceptance at a glance on /healthz (the probe a load balancer
        # reads); the full per-model speculation block rides /metrics
        # via the snapshot's by_model labels
        health = {"decode": pool.health_summary}
        if controller is not None:
            sources["controller"] = controller.snapshot
            health["controller"] = controller.health_summary
        exporter = build_from_args(
            args, sources, "flight_decode.jsonl", health_sources=health)

    tokenizer = engine.tokenizer
    max_new = args.max_new_tokens
    deadline_ms = argv_flags["deadline_ms"]
    # leave generation room inside the slot: the prompt may use at most
    # max_len - max_new positions
    prompt_budget = max(1, engine.max_len - max_new)
    # enough in-flight streams to keep every slot claimable, capped at
    # the waiting-queue bound so pipelining can never walk submissions
    # into the reject tier
    pool_engines = (pool.engines if hasattr(pool, "engines")
                    else [b.engine for b in pool.batchers])
    window = min(2 * sum(e.slots for e in pool_engines),
                 argv_flags["max_queue"])
    inflight: deque = deque()

    def emit(idx, stream) -> None:
        try:
            for tid in stream.tokens(timeout=120):
                print(f"{idx}\ttok\t{tokenizer.vocab_list[tid]}",
                      flush=True)
            print(f"{idx}\tgen\t{detokenize(tokenizer, stream.emitted)}",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — stream failed: report,
            print(f"{idx}\tERROR\t{type(e).__name__}: {e}", flush=True)

    def flush_artifacts() -> None:
        import json

        if controller is not None:
            controller.stop()
        if exporter is not None:
            exporter.stop(final_flight=True)
        snap = pool.snapshot()
        if argv_flags["metrics_path"]:
            from pdnlp_tpu.serve.metrics import _save_json

            _save_json(snap, argv_flags["metrics_path"])
        else:
            rank0_print(json.dumps(snap, indent=2), file=sys.stderr)
        trace_path = engine.tracer.flush()
        if trace_path:
            rank0_print(f"[obs] spans -> {trace_path}", file=sys.stderr)

    n = 0
    try:
        for line in sys.stdin:
            text = line.strip()
            if not text:
                continue
            ids = tokenizer.encode_ids(text, prompt_budget)
            try:
                inflight.append((n, pool.submit_ids(
                    ids, max_new_tokens=max_new,
                    deadline_ms=deadline_ms)))
            except Exception as e:  # noqa: BLE001 — refusal: report
                print(f"{n}\tERROR\t{type(e).__name__}: {e}", flush=True)
                n += 1
                continue
            n += 1
            while len(inflight) >= window:
                emit(*inflight.popleft())
    except _ShutdownRequested as e:
        rank0_print(f"[serve] {e} — draining {len(inflight)} stream(s), "
                    "then shutting down", file=sys.stderr)
    finally:
        while inflight:
            emit(*inflight.popleft())
        pool.stop(drain=True)
        flush_artifacts()


class _ShutdownRequested(KeyboardInterrupt):
    """SIGTERM/SIGINT: stop intake, drain, flush — never drop silently."""


def _install_signal_handlers() -> None:
    def _on_signal(signum, frame):
        raise _ShutdownRequested(signal.Signals(signum).name)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_signal)
        except ValueError:  # non-main thread (embedded use): skip
            return


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    argv, checkpoint = pop_cli_flag(argv, "--checkpoint")
    argv, buckets_s = pop_cli_flag(argv, "--buckets")
    argv, max_batch = pop_cli_flag(argv, "--max_batch_size", 8, int)
    argv, max_wait = pop_cli_flag(argv, "--max_wait_ms", 5.0, float)
    argv, max_queue = pop_cli_flag(argv, "--max_queue", 256, int)
    argv, deadline = pop_cli_flag(argv, "--deadline_ms", None, float)
    argv, replicas = pop_cli_flag(argv, "--replicas", 1, int)
    argv, hedge_ms = pop_cli_flag(argv, "--hedge_ms", None, float)
    argv, stall_s = pop_cli_flag(argv, "--replica_stall_s", 10.0, float)
    argv, serve_pack = pop_cli_flag(argv, "--serve_pack", "auto")
    argv, controller_mode = pop_cli_flag(argv, "--controller", "off")
    argv, min_replicas = pop_cli_flag(argv, "--min_replicas", 1, int)
    argv, fleet_spec = pop_cli_flag(argv, "--fleet")
    argv, shadow_fraction = pop_cli_flag(argv, "--shadow_fraction", 0.0,
                                         float)
    argv, canary_fraction = pop_cli_flag(argv, "--canary_fraction", 0.0,
                                         float)
    argv, degrade_at = pop_cli_flag(argv, "--degrade_at", None, int)
    argv, rollout_mode = pop_cli_flag(argv, "--rollout", "auto")
    argv, speculate = pop_cli_flag(argv, "--speculate")
    argv, draft_k = pop_cli_flag(argv, "--draft_k", 4, int)
    argv, disagg = pop_cli_flag(argv, "--disagg", "off")
    argv, prefill_engines = pop_cli_flag(argv, "--prefill_engines", 1, int)
    argv, decode_engines = pop_cli_flag(argv, "--decode_engines", None, int)
    argv, in_path = pop_cli_flag(argv, "--input")
    argv, out_path = pop_cli_flag(argv, "--output")
    argv, metrics_path = pop_cli_flag(argv, "--metrics_path")
    no_mesh = "--no_mesh" in argv
    if no_mesh:
        argv.remove("--no_mesh")
    decode_mode = "--decode" in argv
    if decode_mode:
        argv.remove("--decode")
    args = parse_cli(argv, base=Args())
    buckets = (tuple(int(b) for b in buckets_s.split(",")) if buckets_s
               else DEFAULT_BUCKETS)
    if decode_mode:
        # generative serving: its own pool/loop — the classifier flags
        # that have no decode meaning are rejected up front
        if fleet_spec or in_path or serve_pack != "auto":
            sys.exit("serve_tpu: --decode is the generative online path — "
                     "drop --fleet/--input/--serve_pack")
        _install_signal_handlers()
        if disagg == "on":
            disagg = "local"  # "on" is shorthand for same-host handoff
        if disagg not in ("off", "local", "socket"):
            sys.exit("serve_tpu: --disagg takes off|local|socket")
        if disagg != "off" and decode_engines is not None:
            # explicit pool sizes: the fleet is their sum; --replicas (if
            # also given) must agree rather than silently losing engines
            total = prefill_engines + decode_engines
            if replicas not in (1, total):
                sys.exit("serve_tpu: --replicas disagrees with "
                         "--prefill_engines + --decode_engines")
            replicas = total
        return serve_decode(args, {
            "replicas": replicas, "checkpoint": checkpoint,
            "use_mesh": not no_mesh, "buckets": buckets,
            "max_queue": max_queue, "metrics_path": metrics_path,
            "deadline_ms": deadline, "speculate": speculate,
            "draft_k": draft_k, "controller": controller_mode,
            "disagg": disagg, "prefill_engines": prefill_engines,
        })
    if speculate:
        sys.exit("serve_tpu: --speculate is the generative path — "
                 "speculative decoding needs --decode")
    if disagg != "off" or decode_engines is not None:
        sys.exit("serve_tpu: --disagg splits the generative decode fleet — "
                 "it needs --decode")
    # chunked prefill (--serve_long_widths "512,1024"): single-replica
    # frontend only — the router's queues stay short-width; a long request
    # hitting a router deployment truncates at the largest bucket as before
    long_widths = tuple(int(w) for w in
                        str(args.serve_long_widths or "").split(",")
                        if str(w).strip())
    if long_widths and (replicas > 1 or fleet_spec):
        sys.exit("serve_tpu: --serve_long_widths is the single-replica "
                 "DynamicBatcher path (chunked prefill); drop it or run "
                 "--replicas 1 without --fleet")

    from pdnlp_tpu.data.corpus import id2label

    _install_signal_handlers()

    if fleet_spec and in_path:
        sys.exit("serve_tpu: --fleet is the online multi-model path; "
                 "offline --input scoring serves ONE model — drop one")

    router = None
    fleet = None
    if fleet_spec and not in_path:
        # the multi-model fleet path: --fleet replaces --replicas (each
        # spec names its own replica count); packed serving stays per
        # group, shadow/canary/degrade ride the FleetRouter front door
        from pdnlp_tpu.serve import parse_fleet_spec

        if replicas > 1:
            sys.exit("serve_tpu: --fleet and --replicas are exclusive — "
                     "each fleet spec entry names its own replica count "
                     "(id=checkpoint:dtype:replicas:role)")
        fleet = build_fleet(
            args, parse_fleet_spec(fleet_spec), use_mesh=not no_mesh,
            buckets=buckets, max_batch_size=max_batch,
            max_wait_ms=max_wait, max_queue=max_queue,
            deadline_ms=deadline, hedge_ms=hedge_ms,
            stall_timeout=stall_s, serve_pack=serve_pack,
            shadow_fraction=shadow_fraction,
            canary_fraction=canary_fraction, degrade_at=degrade_at)
        engine = fleet.engine(0)  # metrics/tracer anchor
    elif replicas > 1 and not in_path:
        router = build_router(
            args, replicas, checkpoint=checkpoint, use_mesh=not no_mesh,
            buckets=buckets, max_batch_size=max_batch, max_wait_ms=max_wait,
            max_queue=max_queue, deadline_ms=deadline, hedge_ms=hedge_ms,
            stall_timeout=stall_s, serve_pack=serve_pack)
        engine = router.engine(0)  # metrics/tracer anchor
    else:
        engine = build_engine(args, checkpoint=checkpoint,
                              use_mesh=not no_mesh)

    pool = fleet if fleet is not None else router
    # the feedback control plane rides the multi-replica router (or the
    # fleet, whose primary group carries the same tuning surface — plus
    # the rollout law when a candidate model is declared); it starts
    # AFTER warmup below so its first sense window never reads compile
    # time as serving latency
    controller = None
    if controller_mode not in ("off", "false", "0", None):
        if pool is None:
            rank0_print("WARNING: --controller needs --replicas N > 1 or "
                        "--fleet (online mode) — running without a "
                        "control plane", file=sys.stderr)
        else:
            from pdnlp_tpu.serve.controller import RolloutPlan, ServeController

            rollout = None
            if fleet is not None and fleet.candidate is not None \
                    and rollout_mode not in ("off", "false", "0"):
                rollout = RolloutPlan()
            controller = ServeController(pool,
                                         min_replicas=min_replicas,
                                         rollout=rollout,
                                         tracer=engine.tracer)

    # live telemetry (--metrics_port / --flight_recorder): Prometheus
    # /metrics + JSON /healthz off the hot path, plus the bounded
    # flight-recorder JSONL so a SIGKILL'd server still leaves evidence
    exporter = None
    if args.metrics_port or args.flight_recorder:
        from pdnlp_tpu.obs import memory_snapshot
        from pdnlp_tpu.obs.exporter import build_from_args

        sources = ({"serve": pool.snapshot} if pool is not None
                   else {"serve": engine.metrics.snapshot,
                         "memory": engine.memory_snapshot})
        if pool is not None:
            sources["memory"] = memory_snapshot
        health = None
        if fleet is not None:
            # per-model role/traffic-split/parity at a glance on /healthz
            # (the full per-model metric labels ride /metrics via the
            # snapshot's `models` block)
            health = {"fleet": fleet.health_summary}
        if controller is not None:
            # controller state on BOTH surfaces: full knob/hold/revert
            # detail as a /metrics source, the at-a-glance summary on
            # /healthz (the probe a load balancer reads)
            sources["controller"] = controller.snapshot
            health = {**(health or {}),
                      "controller": controller.health_summary}
        exporter = build_from_args(args, sources, "flight_serve.jsonl",
                                   health_sources=health)
        if exporter is not None and exporter.port is not None:
            rank0_print(f"[obs] /metrics + /healthz on "
                        f"http://127.0.0.1:{exporter.port}",
                        file=sys.stderr)

    def flush_artifacts(extra=None) -> None:
        """Metrics snapshot + trace spans land on disk on EVERY exit path
        — a drained shutdown that loses its telemetry only half happened."""
        import json

        if exporter is not None:
            exporter.stop(final_flight=True)  # last flight line first
        snap = pool.snapshot() if pool is not None \
            else {**engine.metrics.snapshot(),
                  "memory": engine.memory_snapshot()}
        if extra:
            snap = {**snap, **extra}
        if metrics_path:
            from pdnlp_tpu.serve.metrics import _save_json

            _save_json(snap, metrics_path)
            rank0_print(f"metrics snapshot -> {metrics_path}",
                        file=sys.stderr)
        else:
            rank0_print(json.dumps(snap, indent=2), file=sys.stderr)
        trace_path = engine.tracer.flush()
        if trace_path:
            rank0_print(f"[obs] spans -> {trace_path}", file=sys.stderr)

    if in_path:  # offline: whole-file throughput path
        from pdnlp_tpu.serve.offline import score_file

        try:
            texts, preds, _ = score_file(engine, in_path, buckets=buckets,
                                         batch_size=max_batch)
            out = open(out_path, "w", encoding="utf-8") if out_path \
                else sys.stdout
            try:
                for text, p in zip(texts, preds):
                    out.write(f"{int(p)}\t{id2label[int(p)]}\t{text}\n")
            finally:
                if out_path:
                    out.close()
            rank0_print(f"scored {len(texts)} texts", file=sys.stderr)
        finally:
            flush_artifacts()
        return

    # online: stdin lines through the dynamic batcher (or the router /
    # the fleet — both carry the same start/wait_ready/submit surface)
    if pool is not None:
        frontend = pool.start()
        if not pool.wait_ready():
            frontend.stop(drain=False)
            sys.exit("serve_tpu: no replica finished warmup — the pool is "
                     "dead (corrupt checkpoint? every worker's warm load "
                     "failed?); refusing to serve nothing")
        if controller is not None:
            controller.start()
            rank0_print("[controller] feedback control plane on "
                        f"(min_replicas={min_replicas}; decisions land in "
                        "the trace — trace_tpu.py decisions)",
                        file=sys.stderr)
    else:
        frontend = DynamicBatcher(
            engine, buckets=buckets, max_batch_size=max_batch,
            max_wait_ms=max_wait, max_queue=max_queue,
            default_deadline_ms=deadline, serve_pack=serve_pack,
            pack_max_segments=getattr(args, "pack_max_segments", 16),
            long_widths=long_widths,
        ).start()
        # warmup over the batcher's OWN resolved shapes: one definition of
        # "usable" buckets AND of the pack mode (batcher.resolve_serve_pack
        # / usable_buckets), zero drift between warmup and live traffic
        frontend.warmup()
    rank0_print("ready — one text per line on stdin "
                "(EOF to exit)", file=sys.stderr)

    # pipelined: keep a window of requests in flight so the batcher can
    # actually form multi-row batches (submit-then-block per line would
    # hold queue depth at 1 and micro-batching would never engage);
    # results still print in input order
    from collections import deque

    # the window must scale with the POOL's batch appetite: N replicas
    # each flushing a PADDED batch (flush_rows, the mesh data-axis
    # multiple) need N x that depth in flight before size-triggered
    # batching can engage on any one of them; the single-replica
    # batcher's max_batch_size is already padded in its __init__.  On the
    # packed path the appetite is a TOKEN budget — rows x width real
    # tokens, i.e. up to rows x max_segments short requests per flush —
    # so the window scales to the segment capacity instead, CAPPED at
    # max_queue requests: packed admission is max_queue x width token
    # slots, and a window of W requests can pin up to W x width pending
    # tokens when inputs run long — an uncapped window would walk every
    # submission into the reject tier on a long-text workload the padded
    # path serves fine
    if pool is not None:
        # the fleet's window is sized to its PRIMARY pool (caller traffic
        # lands there; candidate/cheap absorb policy-routed overflow)
        group = fleet.groups[fleet.primary] if fleet is not None else router
        n_rep = len(group._slots)
        rows = group.engine(0).pad_rows(max_batch)
        per_replica = rows * (group.pack_segments if group.packed else 1)
        window = min(2 * n_rep * per_replica, max_queue)
    else:
        window = min(2 * frontend.max_batch_size
                     * (frontend.pack_segments if frontend.packed else 1),
                     max_queue)
    inflight: deque = deque()

    def emit(fut) -> None:
        try:
            logits = fut.result(timeout=60)
        except Exception as e:  # noqa: BLE001 — QueueFullError,
            # DeadlineExceeded, engine failure: report, keep serving
            print(f"ERROR\t{type(e).__name__}: {e}", flush=True)
            return
        p = int(logits.argmax())
        print(f"{p}\t{id2label[p]}", flush=True)

    try:
        for line in sys.stdin:
            text = line.strip()
            if not text:
                continue
            try:
                inflight.append(frontend.submit(text))
            except Exception as e:  # noqa: BLE001 — queue full: report
                print(f"ERROR\t{type(e).__name__}: {e}", flush=True)
                continue
            while len(inflight) >= window:
                emit(inflight.popleft())
    except _ShutdownRequested as e:
        rank0_print(f"[serve] {e} — draining {len(inflight)} in-flight "
                    "request(s), then shutting down", file=sys.stderr)
    finally:
        # graceful shutdown: the controller stops actuating FIRST (and
        # resolves its pending decision evaluations so the flushed trace
        # validates), then every accepted request is completed or
        # deadline-failed through emit() — never silently dropped — then
        # the frontend drains its queues and telemetry hits disk
        if controller is not None:
            controller.stop()
        while inflight:
            emit(inflight.popleft())
        frontend.stop(drain=True)
        flush_artifacts()


if __name__ == "__main__":
    main()
