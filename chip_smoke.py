#!/usr/bin/env python
"""The standing proof that the trainer and the server start on a TPU chip.

    python chip_smoke.py            # one chip: device, train, train-packed,
                                    # serve, decode-kernel, decode,
                                    # decode-latent,
                                    # decode-latent-mhc,
                                    # decode-latent-dsa, decode-hybrid
    python chip_smoke.py --chips 4  # four chips: device, mesh-train (dp and
                                    # zero against one device), replicas,
                                    # decode-mesh

One process, no children that need the chip.  Every phase drives the entry
point a user would call (``train.run.build_parallel_trainer`` — the path
every ``multi-tpu-*.py`` runs — and ``serve_tpu.main``) at
``bert-base``'s full width over a corpus, a vocabulary and a checkpoint this
run makes from ``--seed`` under ``--out``; nothing that merely lies in the
checkout (``output/``, ``*.msgpack``, a prebuilt ``libwordpiece.so``) is read.

Each phase prints one JSON line when it finishes (seconds, compile seconds,
what it resolved).  A phase that raises ends the run non-zero: nothing here
catches an exception to go on.  The LAST stdout line, alone, is the
contract's ``{"ok": true, "device": {...}}`` — printed only after every phase
passed, only on a TPU.

``--rehearse`` walks the same phases on whatever backend is there (the CPU,
``bert-tiny``, kernels forced on in interpret mode) to find wrong paths and
arguments before a chip call.  It skips the checks only a chip can pass,
never prints the ``ok`` line and always exits 3.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import io
import json
import os
import shutil
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# bf16 keeps 8 mantissa bits (eps 2^-8 ~ 4e-3 per rounding); twelve layers of
# them separate two attention/CE implementations by about a percent.  Set
# before the first chip run, from the dtype, not from what a run showed.
LOSS_RTOL = 2e-2       # first-step loss and global gradient norm, and
                       # per-step losses across meshes
LOGIT_ATOL = 5e-2      # packed vs padded serve logits (values are O(0.1-1))

REHEARSAL_EXIT = 3


class SmokeFailure(AssertionError):
    """A phase's check did not hold (raised, never caught: exit != 0)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(obj) -> None:
    print(json.dumps(obj, ensure_ascii=False), flush=True)


# ------------------------------------------------------------- accounting


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), and the cache's hit/miss counts — read off
    ``jax.monitoring``, so it counts exactly what the run compiled."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self):
        return self.seconds, self.hits, self.misses


def run_phase(ctx, name: str, fn) -> dict:
    """Run one phase and print its line.  Whatever the program itself prints
    goes to stderr: stdout carries the phase lines and nothing else."""
    c0, h0, m0 = ctx.clock.read()
    t0 = time.monotonic()
    with contextlib.redirect_stdout(sys.stderr):
        facts = fn(ctx)
    c1, h1, m1 = ctx.clock.read()
    line = {"phase": name, "seconds": round(time.monotonic() - t0, 2),
            "compile_seconds": round(c1 - c0, 2),
            "cache_hits": h1 - h0, "cache_misses": m1 - m0, **facts}
    emit(line)
    ctx.phases[name] = line
    return line


@contextlib.contextmanager
def captured(module, name: str):
    """Record what ``module.name(...)`` returns while the entry point runs —
    how a phase reaches the engine ``serve_tpu.main`` built (to audit its
    ledger, its placement and its logits) without a second code path."""
    orig = getattr(module, name)
    seen = []

    def wrapper(*a, **kw):
        out = orig(*a, **kw)
        seen.append(out)
        return out

    setattr(module, name, wrapper)
    try:
        yield seen
    finally:
        setattr(module, name, orig)


def run_cli(main, argv, stdin_text: str) -> str:
    """Call an entry point's ``main(argv)`` with ``stdin_text`` on stdin;
    returns what it wrote to stdout.  ``serve_tpu.main`` installs signal
    handlers — restored here so a later phase is not left with them."""
    old_in = sys.stdin
    old_handlers = {s: signal.getsignal(s)
                    for s in (signal.SIGTERM, signal.SIGINT)}
    out = io.StringIO()
    sys.stdin = io.StringIO(stdin_text)
    try:
        with contextlib.redirect_stdout(out):
            main(list(argv))
    finally:
        sys.stdin = old_in
        for s, h in old_handlers.items():
            signal.signal(s, h)
    return out.getvalue()


# ------------------------------------------------------------------ corpus

#: bert-base's published vocabulary rows (chinese-bert-wwm-ext)
VOCAB_ROWS = 21_128
N_LABELS = 6


def alphabet():
    """21,402 distinct CJK characters: all of U+4E00-U+9FA5 (20,902) plus
    the first 500 of Extension A — more than the 21,123 non-special rows
    ``build_vocab`` keeps, so the embedding table comes out full width."""
    return ([chr(c) for c in range(0x4E00, 0x9FA6)]
            + [chr(c) for c in range(0x3400, 0x3400 + 500)])


def label_of(text: str, chars_class: dict) -> int:
    """The corpus's label: the class whose signal characters the text
    holds most of (ties to the lowest) — a function of the text alone, so
    the task is learnable and a loss means something."""
    counts = [0] * N_LABELS
    for ch in text:
        k = chars_class.get(ch)
        if k is not None:
            counts[k] += 1
    return counts.index(max(counts))


def write_corpus(path: str, rows: int, seed: int):
    """A seeded corpus in the reference's ``train.json`` format.  Lengths
    spread up to 126 characters (+[CLS]/[SEP] = 128, so seq 128 is real);
    the first rows walk a permutation of the whole alphabet so every
    character — every vocabulary row — occurs; half of each text is drawn
    from its class's 40 signal characters."""
    import random

    rng = random.Random(seed)
    chars = alphabet()
    signal_chars = chars[:40 * N_LABELS]
    chars_class = {ch: i // 40 for i, ch in enumerate(signal_chars)}
    walk = chars[:]
    rng.shuffle(walk)
    data, texts = [], []
    for i in range(rows):
        n = rng.randint(4, 126) if i % 4 else rng.randint(100, 126)
        k = rng.randrange(N_LABELS)
        body = []
        for _ in range(n):
            if walk:
                body.append(walk.pop())
            elif rng.random() < 0.5:
                body.append(signal_chars[40 * k + rng.randrange(40)])
            else:
                body.append(rng.choice(chars))
        text = "".join(body)
        texts.append(text)
        # pre-tokenized with spaces, like the reference's file
        data.append([" ".join(text), label_of(text, chars_class)])
    check(not walk, f"{rows} rows did not cover the alphabet ({len(walk)} "
                    "characters left)")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, ensure_ascii=False)
    return texts


def request_lines(ctx, n: int, lo: int, hi: int):
    """``n`` seeded request texts of mixed length ``lo..hi`` characters."""
    import random

    rng = random.Random(ctx.seed + 7)
    chars = alphabet()
    return ["".join(rng.choice(chars) for _ in range(rng.randint(lo, hi)))
            for _ in range(n)]


# ------------------------------------------------------------------ phases


def phase_device(ctx) -> dict:
    """Is there a chip?  JAX falls back to the CPU when it finds none, so
    this check is the only thing between a CPU run and a pass."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" and not ctx.rehearse:
        print(f"chip_smoke: JAX found no accelerator (platform "
              f"{d.platform!r}, {len(devices)} device(s)); this check runs "
              "on a TPU only — use `chiprun -- python chip_smoke.py`, or "
              "--rehearse for a CPU walk-through that cannot pass",
              file=sys.stderr)
        sys.exit(2)
    if ctx.chips == 4:
        check(len(devices) == 4,
              f"--chips 4 needs four devices, JAX reports {len(devices)}")

    from pdnlp_tpu.data import native
    from pdnlp_tpu.utils.config import enable_compilation_cache
    from pdnlp_tpu.utils.profiling import bf16_peak

    cache_dir = enable_compilation_cache()
    peak = bf16_peak(d)
    if not ctx.rehearse:
        check(peak is not None, f"profiling.BF16_PEAK_BY_KIND has no entry for "
                                f"device kind {d.device_kind!r}")
    # never bind a binary this run did not build: rebuild from the committed
    # sources, and if that cannot be done take the stale one out of
    # ``native.attach``'s way (what ``make clean`` would have done)
    so = native.build(force=True)
    if so is None and os.path.exists(native._SO):
        os.remove(native._SO)
    ctx.device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devices)}
    return {**ctx.device, "jax": jax.__version__, "cache_dir": cache_dir,
            "bf16_peak_flops": peak,
            "tokenizer": "native, built from csrc/" if so else "python",
            "model": ctx.model, "out": ctx.out}


def base_argv(ctx, *extra) -> list:
    """The flags every entry point gets: the model, bf16, and the files
    this run made."""
    return ["--model", ctx.model, "--dtype", "bfloat16",
            "--max_seq_len", "128", "--seed", str(ctx.seed),
            "--data_path", ctx.corpus, "--vocab_path", ctx.vocab,
            "--output_dir", ctx.out, *extra]


def kernel_argv(ctx) -> list:
    """On the chip the defaults (``auto``) must pick the kernels by
    themselves; the rehearsal has to ask for them."""
    return ["--fused_ce", "pallas", "--attn_impl", "pallas"] \
        if ctx.rehearse else []


def pack_argv(ctx) -> list:
    """Likewise for the server: ``--serve_pack auto`` packs on the chip."""
    return ["--serve_pack", "on", "--attn_impl", "pallas"] \
        if ctx.rehearse else []


def train_and_log(trainer, train_loader):
    """``trainer.train`` with the per-log-line losses collected."""
    from pdnlp_tpu.train.trainer import LoopHooks

    losses = []
    trainer.train(train_loader, None, hooks=LoopHooks(
        on_log=lambda e, s, total, loss: losses.append((s, float(loss)))))
    return losses


def host_leaves(params) -> dict:
    """Host fp32 copies of three leaves far apart in the model."""
    import jax
    import numpy as np

    pick = {"word_embeddings": params["embeddings"]["word"],
            "layer_q_kernel": params["layers"]["q"]["kernel"],
            "classifier_kernel": params["classifier"]["kernel"]}
    return {k: np.asarray(jax.device_get(v), np.float32)
            for k, v in pick.items()}


def custom_calls(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def barrier_probe(n: int) -> dict:
    """Is ``block_until_ready`` a real barrier here?  Enqueue 200 dependent
    ``[n, n]`` matmuls (27 TFLOP at n = 4096, over a tenth of a second of
    any chip): the dispatch must return long before they are done, the
    block must wait for them, and a value fetched after it must find
    nothing left to wait for (``train/trainer.py`` ends its timed loop on
    this)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(
            0, 200, lambda _, a: (a @ a) * jnp.bfloat16(1e-2), x)

    x = jnp.full((n, n), 1e-2, jnp.bfloat16)
    float(work(x)[0, 0])                       # compile, and drain
    t0 = time.monotonic()
    y = work(x)
    t1 = time.monotonic()
    jax.block_until_ready(y)
    t2 = time.monotonic()
    float(y[0, 0])
    t3 = time.monotonic()
    return {"dispatch_s": round(t1 - t0, 4), "block_s": round(t2 - t1, 4),
            "fetch_after_block_s": round(t3 - t2, 4)}


def phase_train(ctx) -> dict:
    import jax
    import numpy as np

    from pdnlp_tpu.ops.fused_ce import resolve_fused_ce
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import parse_cli

    steps, batch, fuse = 16, 64, 4
    n_train, n_dev = steps * batch, 128
    ctx.texts = write_corpus(ctx.corpus, n_train + n_dev, ctx.seed)
    args = parse_cli(base_argv(
        ctx, "--strategy", "dp", "--train_batch_size", str(batch),
        "--fuse_steps", str(fuse), "--data_limit", str(n_train + n_dev),
        "--ratio", str((n_train + 0.5) / (n_train + n_dev)),
        "--log_every", "1", *kernel_argv(ctx)))
    trainer, train_loader, dev_loader = build_parallel_trainer(args, mode="dp")
    vocab_rows = trainer.cfg.vocab_size
    check(vocab_rows == VOCAB_ROWS, f"vocabulary has {vocab_rows} rows, "
                                    f"bert-base publishes {VOCAB_ROWS}")
    check(len(train_loader) == steps, f"{len(train_loader)} steps/epoch")
    fused_ce = resolve_fused_ce(args)
    check(fused_ce == "pallas", f"fused_ce resolved {fused_ce!r}")
    check(trainer.pipeline.mode == "resident",
          f"pipeline resolved {trainer.pipeline.mode!r}")
    calls = custom_calls(trainer.multi_step.lower(
        trainer.state, trainer.pipeline.warmup_batch(fuse)))
    if not ctx.rehearse:
        check(calls > 0, "no tpu_custom_call in the lowered fused step: the "
                         "fused-CE kernel is not in the program")
    before = host_leaves(trainer.state["params"])

    losses = train_and_log(trainer, train_loader)
    dev_loss, dev_acc = trainer.dev(dev_loader)

    check(len(losses) == steps // fuse, f"{len(losses)} logged losses")
    check(all(np.isfinite(l) for _, l in losses) and np.isfinite(dev_loss),
          f"non-finite loss: {losses} dev {dev_loss}")
    step = int(jax.device_get(trainer.state["step"]))
    check(step == steps, f"step counter at {step}, not {steps}")
    after = host_leaves(trainer.state["params"])
    moved = {k: float(np.linalg.norm(after[k] - before[k])) for k in after}
    check(all(v > 0 for v in moved.values()), f"parameters did not move: "
                                              f"{moved}")
    stats = jax.devices()[0].memory_stats()
    peak = int(stats["peak_bytes_in_use"]) if stats else 0
    if not ctx.rehearse:
        check(peak > 0, f"memory_stats() reports no peak: {stats}")

    barrier = barrier_probe(256 if ctx.rehearse else 4096)
    if not ctx.rehearse:
        check(barrier["block_s"] > 10 * barrier["dispatch_s"]
              and barrier["fetch_after_block_s"] < 0.1 * barrier["block_s"],
              f"block_until_ready is not a barrier here: {barrier}")

    ctx.checkpoint = args.ckpt_path()
    back = ckpt.load_params(ctx.checkpoint, trainer.state["params"])
    for k, v in host_leaves(back).items():
        check(np.array_equal(v, after[k]),
              f"checkpoint leaf {k} differs from the trained parameters")
    ctx.trained = after
    return {"steps": step, "batch": batch, "seq": 128, "fuse_steps": fuse,
            "vocab_rows": vocab_rows, "fused_ce": fused_ce,
            "pipeline": trainer.pipeline.mode,
            "attention": trainer._routed_attn(128, False),
            "tpu_custom_calls_in_step": calls,
            # the last loss of each fused group of four; a NaN in any step
            # poisons the parameters and so every later one
            "losses": [round(l, 4) for _, l in losses],
            "dev_loss": round(dev_loss, 4), "dev_accuracy": round(dev_acc, 4),
            "param_delta_l2": {k: round(v, 6) for k, v in moved.items()},
            "hbm_peak_bytes": peak, "barrier": barrier,
            "checkpoint": os.path.relpath(ctx.checkpoint, ctx.out)}


def first_step(trainer, train_loader):
    """(loss, global gradient norm) of the first step on the first batch,
    through the real train step on a COPY of the state (the step donates).
    After one AdamW step from zero moments ``mu = (1 - b1) * g``, so the
    gradient's norm is read off the optimizer state the step returns."""
    import jax
    import jax.numpy as jnp
    import optax

    state = jax.tree_util.tree_map(jnp.copy, trainer.state)
    state, metrics = trainer.train_step(
        state, trainer._first_device_batch(train_loader))
    mu = optax.tree_utils.tree_get(state["opt_state"], "mu")
    gnorm = optax.global_norm(mu) / (1.0 - trainer.args.adam_b1)
    return float(metrics["loss"]), float(gnorm)


def phase_train_packed(ctx) -> dict:
    import numpy as np

    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import parse_cli

    batch = 64

    def build(*impls):
        # a packed row holds nearly two examples of this corpus, so the
        # whole of it makes about ten batches of 64 rows
        args = parse_cli(base_argv(
            ctx, "--strategy", "dp", "--train_batch_size", str(batch),
            "--length_mode", "pack", "--attn_dropout", "0", "--dropout", "0",
            "--data_limit", str(len(ctx.texts)), "--log_every", "1",
            "--ckpt_name", "packed-cls.msgpack", *impls))
        return build_parallel_trainer(args, mode="dp")

    ref, ref_loader, _ = build("--fused_ce", "xla", "--attn_impl", "xla")
    check(ref._routed_attn(128, True) == "xla", "xla pair routed a kernel")
    ref_calls = custom_calls(ref.train_step.lower(
        ref.state, ref._first_device_batch(ref_loader)))
    ref_loss, ref_gnorm = first_step(ref, ref_loader)
    del ref, ref_loader
    gc.collect()

    trainer, train_loader, _ = build(*kernel_argv(ctx))
    attn = trainer._routed_attn(128, True)
    check(attn == "pallas", f"packed attention routed {attn!r}")
    calls = custom_calls(trainer.train_step.lower(
        trainer.state, trainer._first_device_batch(train_loader)))
    if not ctx.rehearse:
        # fused CE alone is a forward and a backward call; flash adds three
        # per layer (forward, dQ, dK/dV)
        check(ref_calls == 0 and calls > 2,
              f"tpu_custom_call count: kernels {calls}, xla pair {ref_calls}")
    loss, gnorm = first_step(trainer, train_loader)
    check(np.isclose(loss, ref_loss, rtol=LOSS_RTOL),
          f"first-step loss: kernels {loss} vs xla {ref_loss}")
    check(np.isclose(gnorm, ref_gnorm, rtol=LOSS_RTOL),
          f"first-step gradient norm: kernels {gnorm} vs xla {ref_gnorm}")

    # the epoch's real steps, forward and backward through the segment kernel
    steps = len(train_loader)
    check(steps >= 8, f"only {steps} packed batches")
    losses = train_and_log(trainer, train_loader)
    check(len(losses) == steps and all(np.isfinite(l) for _, l in losses),
          f"packed losses: {losses}")
    return {"steps": len(losses), "batch_rows": batch, "seq": 128,
            "attention": attn, "pipeline": trainer.pipeline.mode,
            "tpu_custom_calls_in_step": calls,
            "first_step": {"loss": round(loss, 5),
                           "loss_xla": round(ref_loss, 5),
                           "grad_norm": round(gnorm, 5),
                           "grad_norm_xla": round(ref_gnorm, 5),
                           "rtol": LOSS_RTOL},
            "losses": [round(l, 4) for _, l in losses]}


def check_loaded(ctx, engine) -> None:
    """The engine serves the checkpoint the train phase wrote — not the
    init weights ``serve_tpu.py`` falls back to when it finds no file."""
    import numpy as np

    check(engine.checkpoint_path == ctx.checkpoint,
          f"engine loaded {engine.checkpoint_path!r}")
    for k, v in host_leaves(engine.params).items():
        check(np.array_equal(v, ctx.trained[k]),
              f"served leaf {k} is not the trained one")


def labels_of(stdout: str, n: int) -> list:
    rows = [l.split("\t") for l in stdout.splitlines() if l.strip()]
    check(len(rows) == n and all(len(r) == 2 and r[0].isdigit()
                                 for r in rows),
          f"expected {n} `<id>\\t<label>` lines, got: {stdout[:400]!r}")
    return [int(r[0]) for r in rows]


def serve_once(ctx, lines, *flags):
    """``serve_tpu.py`` over ``lines``; returns (labels, the engine it
    built, its batcher, its metrics snapshot)."""
    import serve_tpu

    metrics = os.path.join(ctx.out, "serve_metrics.json")
    with captured(serve_tpu, "build_engine") as engines, \
            captured(serve_tpu, "DynamicBatcher") as batchers:
        out = run_cli(serve_tpu.main, base_argv(
            ctx, "--checkpoint", ctx.checkpoint, "--metrics_path", metrics,
            *flags), "\n".join(lines) + "\n")
    with open(metrics) as f:
        snap = json.load(f)
    check_loaded(ctx, engines[0])
    return labels_of(out, len(lines)), engines[0], batchers[0], snap


def retraces_post_warmup(snap, warmed: int) -> int:
    """A single-engine snapshot counts traces since construction; warm-up
    traces each shape once, so anything past ``warmed`` is a retrace."""
    cc = snap["compile_cache"]
    check(cc["misses"] == warmed, f"live traffic reached a shape warm-up "
                                  f"did not: {cc}, warmed {warmed}")
    return cc["retraces"] - warmed


def phase_serve(ctx) -> dict:
    import numpy as np

    from pdnlp_tpu.data.packing import pack_id_lists
    from pdnlp_tpu.serve.batcher import pick_bucket

    lines = request_lines(ctx, 24, 4, 120)
    packed_labels, packed, front, snap = serve_once(ctx, lines,
                                                    *pack_argv(ctx))
    check(front.packed, "--serve_pack auto resolved to the padded path")
    attn = packed.routed_attn(front.pack_width, segmented=True)
    check(attn == "pallas", f"packed serve attention routed {attn!r}")
    packed_retraces = retraces_post_warmup(snap, 1)
    check(packed_retraces == 0, f"packed server retraced: {snap}")

    padded_labels, padded, pfront, psnap = serve_once(
        ctx, lines, "--serve_pack", "off")
    check(not pfront.packed, "--serve_pack off still packed")
    padded_retraces = retraces_post_warmup(psnap, len(pfront.buckets))
    check(padded_retraces == 0, f"padded server retraced: {psnap}")

    # the two engines' logits for the same lines, through the shapes each
    # server already compiled
    ids = packed.tokenizer.encode_ragged(lines, front.pack_width)
    got = {}
    todo = list(range(len(ids)))
    while todo:
        batch, places = pack_id_lists(
            [ids[i] for i in todo], front.pack_width, front.pack_rows,
            front.pack_segments, pad_id=packed.tokenizer.pad_id)
        logits = packed.infer_packed(batch, segments=sum(
            p is not None for p in places))
        for i, p in zip(todo, places):
            if p is not None:
                got[i] = logits[p[0], p[1]]
        todo = [i for i, p in zip(todo, places) if p is None]
    want = {}
    for b in pfront.buckets:
        group = [i for i in range(len(ids))
                 if pick_bucket(len(ids[i]), pfront.buckets) == b]
        for j in range(0, len(group), pfront.max_batch_size):
            part = group[j:j + pfront.max_batch_size]
            logits = padded.infer_ids([ids[i] for i in part], b,
                                      rows=pfront.max_batch_size)
            want.update(zip(part, logits))
    a = np.stack([got[i] for i in range(len(ids))])
    b = np.stack([want[i] for i in range(len(ids))])
    check(np.isfinite(a).all() and np.isfinite(b).all(), "non-finite logits")
    diff = float(np.abs(a - b).max())
    check(diff <= LOGIT_ATOL, f"packed vs padded logits differ by {diff}")
    return {"requests": len(lines), "serve_pack": "packed",
            "attention": attn, "buckets": list(pfront.buckets),
            "pack_shape": [front.pack_rows, front.pack_width,
                           front.pack_segments],
            "retraces_post_warmup": {"packed": packed_retraces,
                                     "padded": padded_retraces},
            "logits_max_abs_diff": round(diff, 5), "atol": LOGIT_ATOL,
            # a checkpoint 16 steps old has near-flat logits: reported,
            # not gated
            "labels_differ": sum(x != y for x, y in
                                 zip(packed_labels, padded_labels)),
            "logit_span": round(float(np.ptp(b)), 4)}


def phase_decode(ctx) -> dict:
    import serve_tpu

    # four slots and four distinct prompts first: the two repeats can only
    # be admitted once a slot frees, which is after their first copies
    # were prefilled and indexed — a full hit whatever the thread timing
    a, b, c, d = request_lines(ctx, 4, 8, 24)
    prompts = [a, b, c, d, a, b]
    max_new = 16
    metrics = os.path.join(ctx.out, "decode_metrics.json")
    with captured(serve_tpu, "build_decode_pool") as pools:
        out = run_cli(serve_tpu.main, base_argv(
            ctx, "--decode", "--checkpoint", ctx.checkpoint,
            "--decode_slots", "4", "--buckets", "32",
            "--max_new_tokens", str(max_new), "--metrics_path", metrics),
            "\n".join(prompts) + "\n")
    rows = [l.split("\t") for l in out.splitlines() if l.strip()]
    check(not [r for r in rows if r[1] == "ERROR"], f"stream errors: {out}")
    gens = {int(r[0]): r[2] if len(r) > 2 else "" for r in rows
            if r[1] == "gen"}
    n_tok = sum(r[1] == "tok" for r in rows)
    check(sorted(gens) == list(range(len(prompts))),
          f"missing generations: {sorted(gens)}")
    check(gens[4] == gens[0] and gens[5] == gens[1],
          f"repeated prompts generated differently: {gens}")
    engine = pools[0].engine(0)
    check_loaded(ctx, engine)
    with open(metrics) as f:
        rep = json.load(f)["replicas"]["0"]
    check(rep["kv"]["layout"] == "paged", f"kv layout {rep['kv']['layout']}")
    prefix = rep["kv"]["prefix"]
    check(prefix["hits_full"] == 2, f"prefix index: {prefix}")
    retraces = rep["engine"]["compile_cache"]
    leak = engine.leak_check()
    check(leak["ok"] and leak["leaked_pages"] == 0, f"leak check: {leak}")
    return {"prompts": len(prompts), "repeats": 2, "max_new_tokens": max_new,
            "tokens_streamed": n_tok, "kv_layout": "paged",
            "narrowed": "--decode_slots 4 (default 8), --buckets 32 "
                        "(default 32,64,128): prefill shapes "
                        f"{list(engine.prefill_buckets)}",
            "prefix": prefix, "compile_cache": retraces,
            "leak_check": {k: leak[k] for k in
                           ("ok", "leaked_pages", "refcount_mismatches",
                            "stream_owners", "index_entries")}}


#: the paged kernel against float32 (outputs are O(1); the kernel rounds its
#: probabilities and its output to bf16, 2^-9 each) — set from the dtype
PAGED_ATOL = 2e-2


def phase_decode_kernel(ctx) -> dict:
    """``ops/paged.py`` against the gathered form it replaces
    (``jnp.take`` of the rung + ``decoder._attend_folded``) and against the
    same mathematics in float32, at the causal cells' sizes: both row rungs,
    the page rungs' ends, lengths at a page's and a block's edges, dead rows,
    a sentinel tail.  Interpret mode forgives what Mosaic does not (an out-of-bounds
    copy, a wait that no copy answers), so this is the check that counts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pdnlp_tpu.models import decoder
    from pdnlp_tpu.ops import paged
    from pdnlp_tpu.ops.attention import NEG_INF

    N, D, ps = (2, 64, 16) if ctx.rehearse else (12, 64, 16)
    H, L = N * D, 2
    cases = [(8, 2)] if ctx.rehearse else [(192, 8), (192, 32), (16, 8),
                                           (16, 32)]
    rng = np.random.default_rng(ctx.seed)
    worst = {"kernel_vs_f32": 0.0, "gather_vs_f32": 0.0,
             "kernel_vs_gather": 0.0}
    for B, MP in cases:
        P = B * MP + 8                       # a layer's pages
        pools = [jnp.asarray(rng.standard_normal((L * P, ps, H)),
                             jnp.bfloat16) for _ in range(2)]
        q = jnp.asarray(rng.standard_normal((B, 1, N, D)), jnp.bfloat16)
        extent = MP * ps
        lengths = rng.integers(1, extent + 1, B).astype(np.int32)
        lengths[:8] = [1, ps, ps + 1, extent, 0, extent - 1, 0,
                       min(paged.BLOCK + 1, extent)]
        # the second layer's pages, shuffled; past a row's own the sentinel
        # (the next layer's first page, or the pool's end: never read)
        table = rng.permutation(P)[:B * MP].reshape(B, MP).astype(np.int32)
        table = np.where(np.arange(MP)[None] * ps < lengths[:, None],
                         table, P) + P
        ids, lens = jnp.asarray(table), jnp.asarray(lengths)

        def gathered(q, pk, pv, dtype):
            got = [jnp.take(p, ids, axis=0, mode="clip").reshape(
                B, extent, H).astype(dtype) for p in (pk, pv)]
            bias = jnp.where(jnp.arange(extent)[None, None, None]
                             < lens[:, None, None, None], 0.0, NEG_INF)
            return decoder._attend_folded(q.astype(dtype), *got, bias)

        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(functools.partial(
                gathered, dtype=jnp.float32))(q, *pools))
        old = np.asarray(jax.jit(functools.partial(
            gathered, dtype=jnp.bfloat16))(q, *pools), np.float32)
        new = np.asarray(jax.jit(decoder._attend_paged)(
            q, *pools, ids, lens), np.float32)
        live = lengths > 0
        check(not new[~live].any(), f"{B}x{MP}: a dead row's output is not "
                                    "zeros")
        for name, a, b in (("kernel_vs_f32", new, want),
                           ("gather_vs_f32", old, want),
                           ("kernel_vs_gather", new, old)):
            worst[name] = max(worst[name],
                              float(np.abs(a[live] - b[live]).max()))
        check(np.isfinite(new).all(), f"{B}x{MP}: not finite")
    check(worst["kernel_vs_f32"] <= PAGED_ATOL,
          f"paged kernel against float32: {worst} > {PAGED_ATOL}")
    check(worst["kernel_vs_f32"] <= worst["gather_vs_f32"] + 1e-3,
          f"the kernel lost precision against the gathered form: {worst}")
    return {"cases": [f"{B} rows x {MP} pages" for B, MP in cases],
            "max_abs_diff": {k: round(v, 5) for k, v in worst.items()},
            "atol": PAGED_ATOL}


#: the latent family's presets a phase builds: (on the chip, in a rehearsal)
LATENT = ("ax-k1-ep16-share-l2", "ax-k1-share-tiny")
LATENT_MHC = ("xing4-29b-ep1-stage-l2", "xing4-stage-tiny")
LATENT_DSA = ("glm-5.2-ep16-share-l2", "glm52-share-tiny")


def phase_decode_latent(ctx, presets=LATENT, tag="latent") -> dict:
    """The second model family (latent attention over a one-pool page cache,
    sparse experts told which they hold) through the same ``serve_tpu.py
    --decode``: the published widths at one dense + one expert layer, seeded
    weights (the family has no trainer), a repeated prompt.  ``LATENT_MHC``:
    the same family with a four-stream residual mixed by hyper-connections
    and a bias-corrected router, its experts held whole.  ``LATENT_DSA``: the
    same family under a learned sparse attention — an indexer in the dense
    layer whose picks the expert layer reuses, the index keys a second pool
    through the latents' page table."""
    import serve_tpu

    model = presets[1] if ctx.rehearse else presets[0]
    a, b = request_lines(ctx, 2, 8, 24)
    prompts = [a, b, a]
    max_new = 8
    metrics = os.path.join(ctx.out, f"decode_{tag}_metrics.json")
    argv = ["--model", model, "--dtype", "bfloat16", "--max_seq_len", "128",
            "--seed", str(ctx.seed), "--data_path", ctx.corpus,
            "--vocab_path", ctx.vocab, "--decode", "--decode_slots", "2",
            # a directory of its own: no checkpoint of another family in it
            "--output_dir", os.path.join(ctx.out, tag), "--buckets", "32", "--max_new_tokens",
            str(max_new), "--metrics_path", metrics]
    with captured(serve_tpu, "build_decode_pool") as pools:
        out = run_cli(serve_tpu.main, argv, "\n".join(prompts) + "\n")
    rows = [l.split("\t") for l in out.splitlines() if l.strip()]
    check(not [r for r in rows if r[1] == "ERROR"], f"stream errors: {out}")
    gens = {int(r[0]): r[2] if len(r) > 2 else "" for r in rows
            if r[1] == "gen"}
    check(sorted(gens) == [0, 1, 2], f"missing generations: {sorted(gens)}")
    check(gens[2] == gens[0], f"the repeated prompt differs: {gens}")
    engine = pools[0].engine(0)
    # latents alone, or latents and a learned sparse attention's index keys
    n_pools = 2 if engine.cfg.index_n_heads else 1
    check(engine.family.name == "latent_moe"
          and len(engine._pools) == n_pools,
          f"family {engine.family.name}, {len(engine._pools)} pools")
    with open(metrics) as f:
        rep = json.load(f)["replicas"]["0"]
    kv = rep["kv"]
    check(kv["prefix"]["hits_full"] == 1, f"prefix index: {kv['prefix']}")
    # the residual streams are carried, never cached: 2 bytes a value
    cfg = engine.cfg
    check(kv["stream_bytes_a_token"] == cfg.hc_mult * cfg.hidden_size * 2,
          f"streams: {kv['stream_bytes_a_token']} bytes a token")
    check(kv["index_bytes_a_token"]
          == cfg.num_index_layers * cfg.index_cache_width * 2,
          f"index keys: {kv['index_bytes_a_token']} bytes a token")
    leak = engine.leak_check()
    check(leak["ok"] and leak["leaked_pages"] == 0, f"leak check: {leak}")
    return {"model": model, "prompts": len(prompts), "repeats": 1,
            "tokens_streamed": sum(r[1] == "tok" for r in rows),
            "cache_bytes_per_token": engine.token_bytes,
            "stream_bytes_a_token": kv["stream_bytes_a_token"],
            "index_bytes_a_token": kv["index_bytes_a_token"],
            "kv_pool_bytes": kv["kv_pool_bytes"],
            "weights_bytes": kv["weights_bytes"],
            "compile_cache": rep["engine"]["compile_cache"]}


def phase_decode_hybrid(ctx) -> dict:
    """The third model family (gated delta-rule linear attention with a
    per-slot recurrent state beside paged GQA layers, sparse experts told
    which they hold) through the same ``serve_tpu.py --decode``: the
    published widths at ONE period of the layer pattern, seeded weights, a
    repeated prompt — which is prefilled whole again (the family shares no
    prefix) and must generate the same tokens from a re-used slot."""
    import serve_tpu

    model = ("solar-open2-share-tiny" if ctx.rehearse
             else "solar-open2-ep16-share-l4")
    a, b = request_lines(ctx, 2, 8, 24)
    prompts = [a, b, a]
    max_new = 8
    metrics = os.path.join(ctx.out, "decode_hybrid_metrics.json")
    argv = ["--model", model, "--dtype", "bfloat16", "--max_seq_len", "128",
            "--seed", str(ctx.seed), "--data_path", ctx.corpus,
            "--vocab_path", ctx.vocab, "--decode", "--decode_slots", "2",
            # a directory of its own: no checkpoint of another family in it
            "--output_dir", os.path.join(ctx.out, "hybrid"), "--buckets", "32",
            "--max_new_tokens", str(max_new), "--metrics_path", metrics]
    with captured(serve_tpu, "build_decode_pool") as pools:
        out = run_cli(serve_tpu.main, argv, "\n".join(prompts) + "\n")
    rows = [l.split("\t") for l in out.splitlines() if l.strip()]
    check(not [r for r in rows if r[1] == "ERROR"], f"stream errors: {out}")
    gens = {int(r[0]): r[2] if len(r) > 2 else "" for r in rows
            if r[1] == "gen"}
    check(sorted(gens) == [0, 1, 2], f"missing generations: {sorted(gens)}")
    check(gens[2] == gens[0], f"the repeated prompt differs: {gens}")
    engine = pools[0].engine(0)
    cfg = engine.cfg
    check(engine.family.name == "hybrid_linear" and len(engine._pools) == 2
          and len(engine._states) == 2 * cfg.num_linear_layers,
          f"family {engine.family.name}, {len(engine._pools)} pools, "
          f"{len(engine._states)} state arrays")
    check(engine._pools[0].shape[0] == cfg.num_gqa_layers,
          f"pools over {engine._pools[0].shape[0]} layers")
    with open(metrics) as f:
        rep = json.load(f)["replicas"]["0"]
    kv = rep["kv"]
    check(kv["prefix"]["hits_full"] == 0 and kv["prefix"]["misses"] == 0,
          f"prefix index: {kv['prefix']}")
    check(kv["state_pool_bytes"] == engine.slots * engine.state_bytes > 0,
          f"state pool: {kv['state_pool_bytes']}")
    leak = engine.leak_check()
    check(leak["ok"] and leak["leaked_pages"] == 0, f"leak check: {leak}")
    return {"model": model, "prompts": len(prompts), "repeats": 1,
            "tokens_streamed": sum(r[1] == "tok" for r in rows),
            "cache_bytes_per_token": engine.token_bytes,
            "state_bytes_per_slot": engine.state_bytes,
            "kv_pool_bytes": kv["kv_pool_bytes"],
            "state_pool_bytes": kv["state_pool_bytes"],
            "weights_bytes": kv["weights_bytes"],
            "compile_cache": rep["engine"]["compile_cache"]}


# ---------------------------------------------------------- four chips


def bytes_in_use() -> list:
    import jax

    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()]


def shard_devices(x) -> int:
    return len({s.device for s in x.addressable_shards})


def phase_mesh_train(ctx) -> dict:
    """dp and zero on a four-device ``data`` mesh against the same seed and
    global batch on a one-device mesh, all in this process."""
    import jax
    import numpy as np
    import optax

    from pdnlp_tpu.ops.fused_ce import resolve_fused_ce
    from pdnlp_tpu.parallel import make_mesh, shard_fraction
    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import parse_cli

    n = ctx.chips
    steps, global_batch = 8, 128
    n_train = steps * global_batch
    ctx.texts = write_corpus(ctx.corpus, n_train + 128, ctx.seed)

    def run(mode, devices):
        """Build, read memory, train; -> facts (state freed on return)."""
        gc.collect()
        base = bytes_in_use()
        # dropout 0: the hardware rbg stream is not promised to be the
        # same under another partitioning
        args = parse_cli(base_argv(
            ctx, "--strategy", mode, "--num_devices", str(devices),
            "--train_batch_size", str(global_batch // devices),
            "--dropout", "0", "--attn_dropout", "0",
            "--data_limit", str(n_train + 128),
            "--ratio", str((n_train + 0.5) / (n_train + 128)),
            "--log_every", "1", "--ckpt_name", f"mesh{devices}-{mode}.msgpack",
            *(kernel_argv(ctx) if devices == 1 else [])))
        trainer, loader, _ = build_parallel_trainer(args, mode=mode)
        # GSPMD partitions the four-device step and a Mosaic kernel cannot
        # follow it: there ``auto`` has to have resolved to the XLA paths
        want = "pallas" if devices == 1 else "xla"
        check(resolve_fused_ce(trainer.args) == want,
              f"{mode}/{devices}: fused_ce {resolve_fused_ce(trainer.args)}")
        jax.block_until_ready(trainer.state)
        # read NOW: the allocator's peak is per process and never falls,
        # so it cannot compare two modes run one after the other
        held = [b - a for a, b in zip(base, bytes_in_use())]
        batch = trainer._first_device_batch(loader)
        state = trainer.state
        mu = optax.tree_utils.tree_get(state["opt_state"], "mu")

        def big(tree):      # a [L, H, H] leaf zero shards along H
            return tree["layers"]["q"]["kernel"]

        facts = {
            "state_bytes_per_device": held[:devices],
            "shard_fraction": round(shard_fraction(
                state, make_mesh(num_devices=devices)), 4),
            "batch_shard_devices": shard_devices(batch["input_ids"]),
            "param_shard_devices": shard_devices(big(state["params"])),
            "param_shard_rows": int(big(state["params"])
                                    .addressable_shards[0].data.shape[1]),
            "moment_shard_devices": shard_devices(big(mu)),
        }
        text = trainer.train_step.lower(state, batch).compile().as_text()
        # mentions, not ops: XLA:TPU fuses a reduce-scatter into an
        # ``all-reduce-scatter`` fusion, which an exact op match misses
        facts["collectives"] = {k: text.count(k) for k in (
            "all-reduce", "all-gather", "reduce-scatter")}
        facts["fused_ce"] = want
        if not ctx.rehearse:
            check(("tpu_custom_call" in text) == (devices == 1),
                  f"{mode}/{devices}: tpu_custom_call in the step: "
                  f"{'tpu_custom_call' in text}")
        del state, mu, batch
        losses = train_and_log(trainer, loader)
        check(len(losses) == steps and all(np.isfinite(l) for _, l in losses),
              f"{mode}/{devices}: losses {losses}")
        facts["losses"] = [round(l, 5) for _, l in losses]
        facts["checkpoint"] = args.ckpt_path()
        return facts

    one = run("dp", 1)
    out = {"steps": steps, "global_batch": global_batch, "seq": 128,
           "one_device": one}
    for mode in ("dp", "zero"):
        m = out[mode] = run(mode, n)
        check(np.allclose(m["losses"], one["losses"], rtol=LOSS_RTOL),
              f"{mode} losses {m['losses']} vs one device {one['losses']}")
        check(m["batch_shard_devices"] == n,
              f"{mode}: batch on {m['batch_shard_devices']} device(s)")
    dp, zero = out["dp"], out["zero"]
    check(dp["collectives"]["all-reduce"] > 0, f"dp step has no all-reduce: "
                                               f"{dp['collectives']}")
    # XLA:CPU spells zero's gradient reduce-scatter as all-reduce + slice
    check(zero["collectives"]["all-gather"] > 0
          and (zero["collectives"]["reduce-scatter"] > 0 or ctx.rehearse),
          f"zero step collectives: {zero['collectives']}")
    check(zero["param_shard_devices"] == n
          and zero["moment_shard_devices"] == n,
          f"zero state on {zero['param_shard_devices']} / "
          f"{zero['moment_shard_devices']} device(s)")
    check(zero["shard_fraction"] < 1.5 / n,
          f"zero shard fraction {zero['shard_fraction']}")
    if not ctx.rehearse:
        ratio = max(zero["state_bytes_per_device"]) \
            / max(dp["state_bytes_per_device"])
        out["zero_over_dp_bytes"] = round(ratio, 3)
        check(0.2 < ratio < 0.4, f"zero holds {ratio:.2f} of dp's bytes per "
                                 "device, expected about a quarter")
    ctx.checkpoint = dp["checkpoint"]
    return out


def phase_replicas(ctx) -> dict:
    import jax
    import serve_tpu

    n = ctx.chips
    lines = request_lines(ctx, 16, 4, 120)
    metrics = os.path.join(ctx.out, "replicas_metrics.json")
    with captured(serve_tpu, "build_router") as routers:
        out = run_cli(serve_tpu.main, base_argv(
            ctx, "--checkpoint", ctx.checkpoint, "--replicas", str(n),
            "--metrics_path", metrics, *pack_argv(ctx)),
            "\n".join(lines) + "\n")
    labels_of(out, len(lines))
    router = routers[0]
    homes = []
    for i in range(n):
        devs = {d for leaf in jax.tree_util.tree_leaves(
            router.engine(i).params) for d in leaf.devices()}
        check(len(devs) == 1, f"replica {i} spans {devs}")
        homes.append(devs.pop().id)
    check(len(set(homes)) == n, f"replicas share devices: {homes}")
    with open(metrics) as f:
        snap = json.load(f)
    reps = snap["replicas"]
    retraces = {k: r["retraces_post_warmup"] for k, r in reps.items()}
    check(not any(retraces.values()), f"replicas retraced: {retraces}")
    return {"requests": len(lines), "replicas": n, "replica_devices": homes,
            "packed": bool(router.packed),
            "batches": {k: r["batches"] for k, r in reps.items()},
            "retraces_post_warmup": retraces}


def phase_decode_mesh(ctx) -> dict:
    """``serve_tpu.py --decode`` as it starts on a host of several chips
    with its default ``--replicas 1``: ONE engine over a mesh of all of
    them, every program replicated.  Mosaic refuses a kernel in a ``jit``
    over more than one device, at lowering — which no CPU test reaches — so
    the BERT family's decode step must keep its gathered form there
    (``decoder.attend_form``) and say so in its span; with a replica a chip
    (``--replicas <chips>``) it takes the kernel again.  The two generate
    from the same weights; their tokens are compared, not held equal (the
    kernel's scores accumulate in float32, the gathered form's round to
    bfloat16, and a near tie may fall the other way)."""
    import jax
    import serve_tpu

    n = ctx.chips
    prompts = request_lines(ctx, 4, 8, 24)
    max_new = 16
    out = {"prompts": len(prompts), "max_new_tokens": max_new}
    gens = {}
    from pdnlp_tpu.obs.trace import get_tracer

    for replicas, form in ((1, "gather"), (n, "kernel")):
        get_tracer().clear()    # one tracer a process: the leg before's spans
        with captured(serve_tpu, "build_decode_pool") as pools:
            text = run_cli(serve_tpu.main, base_argv(
                ctx, "--decode", "--checkpoint", ctx.checkpoint,
                "--decode_slots", "4", "--buckets", "32",
                "--replicas", str(replicas), "--trace", "true",
                "--max_new_tokens", str(max_new)),
                "\n".join(prompts) + "\n")
        rows = [l.split("\t") for l in text.splitlines() if l.strip()]
        check(not [r for r in rows if r[1] == "ERROR"],
              f"{replicas} replica(s): stream errors: {text}")
        toks = {i: [r[2] for r in rows if r[1] == "tok" and int(r[0]) == i]
                for i in range(len(prompts))}
        check(all(len(t) == max_new for t in toks.values()),
              f"{replicas} replica(s): tokens streamed "
              f"{ {i: len(t) for i, t in toks.items()} }")
        engine = pools[0].engine(0)
        devs = {d.id for leaf in jax.tree_util.tree_leaves(engine.params)
                for d in leaf.devices()}
        check(len(devs) == n // replicas,
              f"{replicas} replica(s): engine 0 spans {sorted(devs)}")
        forms = [r["attrs"].get("attend") for r in engine.tracer.records()
                 if r["name"] == "decode.dispatch"]
        check(forms and set(forms) == {form},
              f"{replicas} replica(s): decode steps took {set(forms)}, "
              f"expected {form}")
        gens[form] = toks
        out[f"replicas_{replicas}"] = {
            "devices_an_engine": len(devs), "attend": form,
            "decode_steps": len(forms)}
    same = [a == b for i in gens["kernel"]
            for a, b in zip(gens["kernel"][i], gens["gather"][i])]
    out["tokens_agree"] = round(sum(same) / len(same), 4)
    check(out["tokens_agree"] >= 0.5,
          f"the two forms disagree on most tokens: {out['tokens_agree']}")
    return out


# --------------------------------------------------------------------- main


class Context:
    def __init__(self, ns):
        self.chips = ns.chips
        self.seed = ns.seed
        self.rehearse = ns.rehearse
        self.model = "bert-tiny" if ns.rehearse else "bert-base"
        self.out = os.path.abspath(ns.out)
        self.corpus = os.path.join(self.out, "data", "train.json")
        self.vocab = os.path.join(self.out, "data", "vocab.txt")
        self.clock = CompileClock()   # imports jax; touches no backend
        self.phases = {}
        self.device = None
        self.checkpoint = None
        self.trained = None
        self.texts = None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the four-chip phases")
    ap.add_argument("--seed", type=int, default=123)
    ap.add_argument("--out", default=os.path.join(HERE, "output",
                                                  "chip_smoke"),
                    help="everything this run writes (emptied first); the "
                         "phase lines are also kept in chiprun_out/")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through at bert-tiny; cannot pass")
    ns = ap.parse_args(argv)
    ctx = Context(ns)
    marker = os.path.join(ctx.out, ".chip_smoke")
    if os.path.isdir(ctx.out) and os.listdir(ctx.out) \
            and not os.path.exists(marker):
        sys.exit(f"chip_smoke: --out {ctx.out} holds files this script did "
                 "not write; it empties its output directory, so name "
                 "another")
    shutil.rmtree(ctx.out, ignore_errors=True)
    os.makedirs(ctx.out)
    open(marker, "w").close()

    run_phase(ctx, "device", phase_device)
    if ctx.chips == 4:
        run_phase(ctx, "mesh-train", phase_mesh_train)
        run_phase(ctx, "replicas", phase_replicas)
        run_phase(ctx, "decode-mesh", phase_decode_mesh)
    else:
        run_phase(ctx, "train", phase_train)
        run_phase(ctx, "train-packed", phase_train_packed)
        run_phase(ctx, "serve", phase_serve)
        run_phase(ctx, "decode-kernel", phase_decode_kernel)
        run_phase(ctx, "decode", phase_decode)
        run_phase(ctx, "decode-latent", phase_decode_latent)
        run_phase(ctx, "decode-latent-mhc", functools.partial(
            phase_decode_latent, presets=LATENT_MHC, tag="latent_mhc"))
        run_phase(ctx, "decode-latent-dsa", functools.partial(
            phase_decode_latent, presets=LATENT_DSA, tag="latent_dsa"))
        run_phase(ctx, "decode-hybrid", phase_decode_hybrid)
    if ctx.rehearse:
        print("chip_smoke: rehearsal walked every phase; this is not a chip "
              "run", file=sys.stderr)
        return REHEARSAL_EXIT
    # checkpoints are too large for what a chip call brings back: only the
    # phase lines go under chiprun_out/
    kept = os.path.join(HERE, "chiprun_out")
    os.makedirs(kept, exist_ok=True)
    with open(os.path.join(kept, f"chip_smoke_{ctx.chips}chip.json"),
              "w") as f:
        json.dump(ctx.phases, f, indent=1, ensure_ascii=False)
    emit({"ok": True, "device": ctx.device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
