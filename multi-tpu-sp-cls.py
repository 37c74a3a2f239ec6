"""Sequence-parallel training over a (data x seq) mesh — the long-context
configuration.

No reference twin exists (``/root/reference`` fixes ``max_seq_len=128`` and
has no sequence/context parallelism, ``SURVEY.md`` §5): this entrypoint is
the capability the TPU framework adds.  Activations shard along the
sequence inside each data shard; attention runs as ring attention over the
ICI ``seq`` ring (``ops.ring``); the classification task stays byte-
compatible with every other strategy.  On the short-sequence corpus it is a
correctness/scale demonstration — its natural use is sequences that do not
fit one device (measured before PR 1 on v5e, record removed, not
re-measured on this code).

Multi-process: the spawn launcher runs this same path with the seq axis
spanning OS processes (``multi-tpu-spawn-cls.py --mode sp``), pinned by
``tests/test_spawn.py``.

    python multi-tpu-sp-cls.py --mesh_shape '{"data": 2, "seq": 4}'
"""
from pdnlp_tpu.train.run import run_sp
from pdnlp_tpu.utils.config import Args, parse_cli

if __name__ == "__main__":
    run_sp(parse_cli(base=Args(strategy="sp")))
