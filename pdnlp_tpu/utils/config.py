"""Single typed hyperparameter config.

The reference duplicates a plain ``Args`` class nine times with drift
(``eval_step`` 100 vs 50: ``single-gpu-cls.py:204`` vs
``multi-gpu-distributed-cls.py:252``; model path ``hfl/...`` vs local
``model_hub/...``: ``multi-gpu-horovod-cls.py:253``).  Here there is ONE
dataclass; strategy entrypoints override fields instead of copy-pasting.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional


_DEFAULT_DATA = "/root/reference/data/train.json"


@dataclasses.dataclass
class Args:
    """Hyperparameters (defaults mirror ``multi-gpu-distributed-cls.py:242-257``)."""

    # --- data ---
    data_path: str = _DEFAULT_DATA
    vocab_path: str = "output/vocab.txt"          # built from the corpus (no egress)
    max_seq_len: int = 128                        # single-gpu-cls.py:196
    data_limit: int = 10_000                      # first-N slice, single-gpu-cls.py:226
    ratio: float = 0.92                           # train/dev split, single-gpu-cls.py:195
    train_batch_size: int = 32                    # per device
    dev_batch_size: int = 32

    # --- model ---
    model: str = "bert-base"                      # key into models.config registry
    num_labels: int = 6
    dropout: float = 0.1
    attn_dropout: float = 0.1                     # attention_probs_dropout_prob
    init_from: Optional[str] = None               # pretrain ckpt: encoder warm-start
    mlm_prob: float = 0.15                        # pretraining mask rate
    mlm_span: bool = True                         # n-gram (wwm-analog) masking
    pretrain_limit: Optional[int] = None          # cap pretrain texts (tests)
    pretrain_ckpt_every: Optional[int] = None     # epoch-curve checkpoints
    sft_epochs: int = 0                           # supervised pretrain stage:
                                                  # epochs over the ~30k labeled
                                                  # examples outside the
                                                  # fine-tune slice (0 = off)
    sft_lr: float = 3e-5                          # its peak learning rate
    init_head: bool = False                       # --init_from also restores
                                                  # pooler+classifier (for
                                                  # supervised-pretrain ckpts)

    # --- optimization (single-gpu-cls.py:86-97,193-205) ---
    learning_rate: float = 3e-5
    label_smoothing: float = 0.0                  # CE target smoothing eps
    ema_decay: float = 0.0                        # >0 keeps an exponential
                                                  # moving average of params
                                                  # on device; eval/best/
                                                  # checkpoint use the EMA
                                                  # weights (jit dp/zero/tp/
                                                  # ep strategies)
    lr_schedule: Optional[str] = None             # warmup_linear|warmup_cosine
    warmup_ratio: float = 0.06                    # fraction of total steps
    weight_decay: float = 0.01
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-6
    epochs: int = 1
    seed: int = 123

    # --- eval / checkpoint ---
    eval_step: int = 50                           # multi-gpu-distributed-cls.py:252
    dev: bool = False                             # eval during training (default off)
    output_dir: str = "output"
    ckpt_name: Optional[str] = None               # default: "<strategy>-cls.msgpack"

    # --- TPU-native knobs (replace AMP / ZeRO / launcher flags) ---
    dtype: str = "float32"                        # "bfloat16" = the AMP analog
    grads_dtype: str = "param"                    # "param": fp32 grads (default).
                                                  # "compute": kernel grads
                                                  # materialize in the compute
                                                  # dtype — measured NEUTRAL
                                                  # to -6% on v5e (XLA re-fuses
                                                  # the assembly worse); kept
                                                  # for A/B (record removed)
    rng_impl: str = "rbg"                         # dropout PRNG (utils.seeding.train_key)
    strategy: str = "single"                      # single|pmap|dp|shardmap|zero|...
    mode: str = "dp"                              # spawn launcher sharding mode:
                                                  # dp|zero|tp|ep (shared runner)
                                                  # or pp (pipeline runner) —
                                                  # lets ONE multi-process
                                                  # launcher execute any
                                                  # placement, incl. shards
                                                  # spanning process boundaries
    remat: bool = False                           # activation checkpointing (ZeRO analog)
    offload_opt_state: bool = False               # Adam moments in host RAM
                                                  # (DeepSpeed offload analog;
                                                  # ~4x step cost, frees ~8
                                                  # bytes/param of HBM)
    attention_impl: str = "auto"                  # auto|xla|pallas (CLI alias
                                                  # --attn_impl).  auto =
                                                  # the measured routing:
                                                  # segment-native pallas
                                                  # flash attention for
                                                  # PACKED batches on a TPU
                                                  # backend (no [B,1,S,S]
                                                  # segment_bias in HBM),
                                                  # XLA elsewhere; dropout
                                                  # and non-128-tiling
                                                  # widths always take XLA
                                                  # (ops.attention
                                                  # .routed_impl)
    fused_ce: str = "auto"                        # auto|xla|pallas: fused
                                                  # classifier-projection +
                                                  # weighted-CE kernel in
                                                  # the train step (ops.
                                                  # fused_ce; logits never
                                                  # round-trip HBM).  auto =
                                                  # pallas on TPU, XLA
                                                  # reference path elsewhere
    serve_dtype: str = "auto"                     # serve forward precision:
                                                  # auto (= --dtype, legacy)
                                                  # | bf16 | int8 (per-
                                                  # channel int8 weights +
                                                  # bf16 activations,
                                                  # serve/quant.py; artifact
                                                  # via scripts/
                                                  # quantize_ckpt.py)
    scan_unroll: Optional[int] = None             # layer-scan unroll; None =
                                                  # full (14% faster step,
                                                  # measured), 1 = lax.scan
                                                  # (flat compile time)
    fuse_steps: int = 1                           # K optimizer steps per dispatch
    num_devices: Optional[int] = None             # cap mesh size (None = all)
    microbatches: int = 4                         # pipeline (pp) microbatch
                                                  # count; bubble is
                                                  # (S-1)/(M+S-1)
    mesh_shape: Optional[dict] = None             # axis name -> size, -1 infers
                                                  # one; the framework shards
                                                  # over "data" (all
                                                  # strategies), "seq" (sp),
                                                  # and "model" (tp), e.g.
                                                  # {"data": 2, "model": 4}
    moe_dispatch: Optional[str] = None            # grouped|dense (None =
                                                  # model-config default;
                                                  # models/config.py)
    moe_capacity_factor: Optional[float] = None   # grouped-dispatch slots
                                                  # per expert multiplier
    moe_top_k: Optional[int] = None               # experts combined/token
    moe_experts: Optional[int] = None             # expert count override
                                                  # (scaling experiments)
    gelu: Optional[str] = None                    # erf|tanh activation
                                                  # (None = model-config
                                                  # default "erf"; tanh
                                                  # measured +7% step rate,
                                                  # models/config.py)
    accel_config: Optional[str] = None            # Accelerator machine-config
                                                  # file (JSON/YAML, the
                                                  # default_config.yaml
                                                  # analog — accel.py)
    length_mode: str = "auto"                     # length-aware training
                                                  # (data/sampler.py):
                                                  # full (pad every batch to
                                                  # max_seq_len — reference
                                                  # semantics) | bucket
                                                  # (length-grouped batches
                                                  # padded to the smallest
                                                  # covering bucket) | pack
                                                  # (multiple examples per
                                                  # row, block-diagonal
                                                  # attention).  auto = full:
                                                  # bucket/pack change batch
                                                  # COMPOSITION (not per-
                                                  # example math), so they
                                                  # are opt-in
    length_buckets: str = "32,64,128"             # bucket widths; values over
                                                  # max_seq_len are dropped
                                                  # and max_seq_len is always
                                                  # the last bucket
    pack_max_segments: int = 16                   # examples per packed row
                                                  # cap (static shape of the
                                                  # per-segment channels) at
                                                  # the 128-token base width;
                                                  # wider rows scale linearly
                                                  # (data.packing.segment_cap)
    serve_long_widths: str = ""                   # chunked-prefill widths for
                                                  # the online batcher, e.g.
                                                  # "512,1024": requests over
                                                  # the pack width ride
                                                  # long-width packed flushes
                                                  # interleaved behind short
                                                  # traffic (serve/batcher.py;
                                                  # "" = long requests
                                                  # truncate at the largest
                                                  # bucket, the legacy path)
    decode_slots: int = 8                         # generative serving
                                                  # (serve/decode.py): KV-
                                                  # cache slots = the fixed
                                                  # decode batch rows;
                                                  # continuous batching
                                                  # keeps them full
    decode_max_len: int = 0                       # per-slot KV positions
                                                  # (prompt + generated);
                                                  # 0 = max_seq_len
    max_new_tokens: int = 32                      # default generation
                                                  # budget per stream
    kv_dtype: str = "auto"                        # KV-cache precision:
                                                  # auto (= the serve
                                                  # compute dtype) | fp32 |
                                                  # bf16 | int8 (per-
                                                  # channel scale tables —
                                                  # calibrated at warmup or
                                                  # loaded from scripts/
                                                  # quantize_ckpt.py
                                                  # --kv_calib)
    kv_hbm_mb: float = 0.0                        # declared KV-cache HBM
                                                  # budget per decode
                                                  # engine (obs.memory.
                                                  # KVBudget): caps pages at
                                                  # construction, loud
                                                  # refusal (never OOM) at
                                                  # admission; 0 = off
    kv_layout: str = "paged"                      # one value: the benchmark
                                                  # still passes it (ROADMAP,
                                                  # Design); anything else is
                                                  # refused in __post_init__
    kv_page_sz: int = 16                          # KV
                                                  # positions per page (the
                                                  # sharing granularity —
                                                  # prefixes share in whole
                                                  # pages, copy-on-write at
                                                  # the divergence page)
    prefetch: int = 2                             # loader collation lookahead
    pipeline: str = "auto"                        # input pipeline (data/
                                                  # pipeline.py): auto|
                                                  # resident (split held in
                                                  # HBM, zero per-step
                                                  # transport)|prefetch
                                                  # (double-buffered upload)
                                                  # |sync (reference-style
                                                  # put-in-loop).  auto =
                                                  # resident when eligible,
                                                  # else prefetch
    pipeline_hbm_mb: int = 128                    # resident-mode budget: the
                                                  # encoded split must fit
                                                  # this many MB of HBM
    log_every: int = 1
    trace: bool = False                           # obs span tracing (pdnlp_
                                                  # tpu.obs): per-step phase
                                                  # spans + breakdown +
                                                  # regression detector;
                                                  # off by default, <2%
                                                  # steps/s when on
    trace_dir: Optional[str] = None               # span files (trace_proc
                                                  # <i>.jsonl); default
                                                  # <output_dir>/trace
    metrics_port: int = 0                         # live telemetry (obs.
                                                  # exporter): Prometheus
                                                  # /metrics + JSON
                                                  # /healthz on this port,
                                                  # served off the hot
                                                  # path; 0 = off.  Also
                                                  # turns on the flight
                                                  # recorder (default
                                                  # path under
                                                  # <output_dir>/telemetry)
    flight_recorder: Optional[str] = None         # bounded JSONL a
                                                  # background thread
                                                  # appends metric
                                                  # snapshots to, so a
                                                  # SIGKILL'd run leaves
                                                  # evidence; settable
                                                  # without --metrics_port
    profile_dir: Optional[str] = None             # jax.profiler trace output
    warmup_compile: bool = False                  # AOT-compile steps before
                                                  # the timed epoch (bench
                                                  # methodology; the warm-
                                                  # CUDA-context analog)
    probe_steps: int = 0                          # N re-fed steps probed
                                                  # before the epoch; prints
                                                  # the controlled steps/s

    # --- multi-host runtime (NCCL/TCPStore rendezvous analog) ---
    coordinator_address: Optional[str] = None     # e.g. "localhost:12345"
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    # --- failure detection / elastic restart (parallel/watchdog.py) ---
    resume_every: Optional[int] = None            # full-state snapshot every N steps
    resume_from: Optional[str] = None             # snapshot path, or "auto"
    ckpt_async: bool = True                       # resume snapshots: device->
                                                  # host copy in-loop, msgpack
                                                  # + atomic publish on a
                                                  # writer thread (train/
                                                  # async_ckpt.py; at most
                                                  # one save in flight).
                                                  # false = synchronous save
                                                  # back in the step loop
    heartbeat_interval: float = 0.0               # seconds; 0 = no heartbeat
    elastic: bool = False                         # spawn launcher: restart on failure
    elastic_shrink: bool = True                   # evict DEAD ranks and
                                                  # resume the gang at the
                                                  # surviving width (the
                                                  # degrade-don't-die
                                                  # policy); false = always
                                                  # restart at full width
                                                  # (bitwise layout-matched
                                                  # continuation)
    min_processes: int = 1                        # never shrink the gang
                                                  # below this width
    stall_timeout: float = 300.0                  # launcher stall detector
                                                  # (pre-first-beat grace is
                                                  # 4x this, covering compile)
    max_restarts: int = 2                         # gang restarts before giving up
    restart_backoff: float = 1.0                  # seconds before restart 1;
                                                  # doubles per restart
    restart_backoff_cap: float = 30.0             # exponential backoff ceiling

    def __post_init__(self) -> None:
        if self.kv_layout != "paged":
            raise ValueError(
                f"kv_layout={self.kv_layout!r}: the slot KV layout is gone "
                "(PR 29); the decode cache is paged, and 'paged' is the "
                "only value this field takes")

    def replace(self, **kw) -> "Args":
        return dataclasses.replace(self, **kw)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, ensure_ascii=False)

    @classmethod
    def from_json(cls, s: str) -> "Args":
        d = json.loads(s)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def ckpt_path(self, name: Optional[str] = None) -> str:
        """One checkpoint per strategy, like the reference's per-script
        ``*.pt`` files that ``test.py:85-94`` sweeps."""
        return os.path.join(self.output_dir,
                            name or self.ckpt_name or f"{self.strategy}-cls.msgpack")

    def resume_path(self) -> str:
        """Where periodic full-state snapshots live (``resume_from="auto"``)."""
        if self.resume_from and self.resume_from != "auto":
            return self.resume_from
        return os.path.join(self.output_dir, f"resume-{self.strategy}.msgpack")


def add_dataclass_args(parser, cls, defaults=None) -> None:
    """Add one typed ``--field`` per dataclass field: Optional[T] unwraps to
    T, bools accept 1/true/yes, and structured fields (dicts/lists) parse as
    JSON — loud failure on malformed input beats silent str-typing.  Shared
    by ``parse_cli`` (Args) and the AutoTrainer entrypoint (TrainerArgs)."""
    import types
    import typing

    hints = typing.get_type_hints(cls)
    for f in dataclasses.fields(cls):
        if defaults is not None:
            default = getattr(defaults, f.name)
        elif f.default is not dataclasses.MISSING:
            default = f.default
        elif f.default_factory is not dataclasses.MISSING:
            default = f.default_factory()
        else:
            default = None  # required field: argparse surfaces the miss
        hint = hints.get(f.name, str)
        # Unwrap Optional[T] so `--num_processes 4` parses as int, not "4".
        if typing.get_origin(hint) in (typing.Union, types.UnionType):
            inner = [a for a in typing.get_args(hint) if a is not type(None)]
            hint = inner[0] if len(inner) == 1 else str
        if hint is bool:
            parser.add_argument(f"--{f.name}",
                                type=lambda s: s.lower() in ("1", "true", "yes"),
                                default=default)
        elif hint in (int, float, str):
            parser.add_argument(f"--{f.name}", type=hint, default=default)
        else:
            parser.add_argument(f"--{f.name}", type=json.loads, default=default)


#: the one in-checkout compile cache, anchored on this file (NOT the working
#: directory, NOT ``--output_dir``): the directory is part of XLA's cache key,
#: so a cache that moves with either never hits
COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache")


def enable_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins and nothing is set
    in code (JAX read it at import) — that is how a deployment, or the chip
    tool, places the cache.  Otherwise every entrypoint shares
    :data:`COMPILATION_CACHE_DIR`, so repeat runs of any of them skip the
    20-40 s first compile of a bert-base step.  Callable before or after
    backend init; ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test suite)
    leaves the directory set but unused."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILATION_CACHE_DIR)
    return COMPILATION_CACHE_DIR


def pop_cli_flag(argv, name: str, default=None, cast=str):
    """``(argv_without_the_pair, value)`` for a script-local ``--name value``
    flag that is NOT an ``Args`` field (``serve_tpu.py``'s).  The
    returned argv is a new list; the input is not mutated."""
    argv = list(argv)
    if name in argv:
        i = argv.index(name)
        if i + 1 >= len(argv):
            raise SystemExit(f"{name} requires a value")
        value = cast(argv[i + 1])
        return argv[:i] + argv[i + 2:], value
    return argv, default


def parse_cli(argv=None, base: Optional[Args] = None) -> Args:
    """``--key value`` CLI overrides onto an ``Args`` (argparse analog of
    ``multi-gpu-distributed-cls.py:374-381``)."""
    import argparse

    p = argparse.ArgumentParser()
    add_dataclass_args(p, Args, defaults=base or Args())
    # short alias for the kernel escape hatch (README "Kernels" section);
    # SUPPRESS keeps the primary --attention_impl default authoritative
    p.add_argument("--attn_impl", dest="attention_impl", type=str,
                   default=argparse.SUPPRESS,
                   help="alias for --attention_impl (auto|xla|pallas)")
    ns = p.parse_args(argv)
    enable_compilation_cache()
    try:
        return Args(**vars(ns))
    except ValueError as e:  # a field's one-sentence refusal (__post_init__)
        p.error(str(e))
