"""High-throughput offline scoring — the whole-file batch path.

The online batcher optimizes tail latency; this path optimizes throughput
over a corpus that is fully known up front.  Same bucketing, no queueing:
texts are encoded ragged, grouped by covering bucket, chunked into
fixed-shape batches, and results are re-assembled in input order — so it is
deterministic, which makes it the parity surface ``tests/test_serve.py``
drives (and a useful tool in its own right:
``serve_tpu.py --input file.txt``).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from pdnlp_tpu.serve.batcher import DEFAULT_BUCKETS, pick_bucket, usable_buckets
from pdnlp_tpu.serve.engine import InferenceEngine


def score_texts(
    engine: InferenceEngine,
    texts: Sequence[str],
    *,
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    batch_size: int = 8,
) -> Tuple[np.ndarray, np.ndarray]:
    """(preds ``[N]``, logits ``[N, num_labels]``) in input order.

    Bucket-grouping maximizes compile-cache hits exactly like the online
    path: every batch is ``(bucket, padded_rows)``-shaped, so after one
    batch per bucket the engine never retraces.  Batch occupancy lands in
    the shared metrics (a mostly-short-text corpus in big buckets shows up
    as low occupancy, the signal to re-tune the bucket list).
    """
    usable = usable_buckets(buckets, engine.args.max_seq_len)
    # encode truncates to the LARGEST bucket (batcher.submit semantics):
    # every row is guaranteed to fit the bucket pick_bucket assigns it
    ids = engine.tokenizer.encode_ragged(texts, usable[-1])
    by_bucket: dict = {}
    for i, row in enumerate(ids):
        by_bucket.setdefault(pick_bucket(len(row), usable), []).append(i)

    num_labels = engine.cfg.num_labels
    logits = np.zeros((len(texts), num_labels), np.float32)
    rows = engine.pad_rows(batch_size)
    for bucket in sorted(by_bucket):
        order = by_bucket[bucket]
        for start in range(0, len(order), rows):
            chunk = order[start : start + rows]
            engine.metrics.requests_total.inc(len(chunk))
            t0 = time.monotonic()
            out = engine.infer_ids([ids[i] for i in chunk], bucket, rows=rows)
            batch_ms = (time.monotonic() - t0) * 1e3
            engine.metrics.batches_total.inc()
            engine.metrics.batch_occupancy.observe(len(chunk) / rows)
            for j, i in enumerate(chunk):
                # offline "latency" is the batch's execution time: no queue
                # wait exists here, and per-row attribution of a fused
                # dispatch is not meaningful
                engine.metrics.request_latency_ms.observe(batch_ms)
                logits[i] = out[j]
    return np.argmax(logits, axis=-1), logits


def score_file(
    engine: InferenceEngine,
    path: str,
    *,
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    batch_size: int = 8,
    limit: Optional[int] = None,
) -> Tuple[List[str], np.ndarray, np.ndarray]:
    """Classify a text file (one UTF-8 text per line, blanks skipped):
    returns (texts, preds, logits)."""
    with open(path, encoding="utf-8") as f:
        texts = [line.strip() for line in f if line.strip()]
    if limit is not None:
        texts = texts[:limit]
    preds, logits = score_texts(engine, texts, buckets=buckets,
                                batch_size=batch_size)
    return texts, preds, logits
