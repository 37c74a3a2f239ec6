"""Checkpoint save/load (msgpack over pytrees).

Reference behavior being covered:
- rank-0 ``torch.save(model.state_dict())`` at end / on best dev accuracy
  (``/root/reference/multi-gpu-distributed-cls.py:192,196-197``);
- loading with the ``module.``-prefix strip (``/root/reference/test.py:96-101``)
  — a non-problem here because pytree keys never grow wrapper prefixes;
- DeepSpeed's sharded engine checkpoints + ``zero_to_fp32.py`` consolidation
  (``/root/reference/README.md:481-485``) — covered by ``consolidate``, which
  all-gathers sharded ``jax.Array`` leaves to host numpy before serializing,
  so a ZeRO-sharded run writes the same single-file format as a single-chip
  run and every checkpoint loads everywhere.

Beyond the reference: ``save_state`` persists optimizer state + step + RNG
key, enabling true mid-training resume (the reference cannot resume).

Durability contract (what a PUBLISHED snapshot promises):

- every write is crash-atomic — bytes land in ``<path>.tmp`` and are
  ``os.replace``d into place, so a reader can never observe a torn file;
- every publish also writes ``<path>.manifest.json`` (atomically, after the
  data) carrying the file's byte count and CRC32 — :func:`load` re-verifies
  both, so silent truncation/corruption (host crash before the page cache
  drained, disk-full, bit rot) is DETECTED instead of surfacing as an
  opaque msgpack error three layers later;
- the previously published snapshot survives as ``<path>.prev`` (retained
  via hardlink before the new data replaces ``path``) — a verified-corrupt
  ``path`` falls back to it with a loud warning instead of crashing the
  resume, losing at most one snapshot interval of progress.

The split :func:`snapshot` (device→host, collective) / :func:`publish`
(serialize + atomic write, host-only) is what the async checkpointer
(``train/async_ckpt.py``) builds on: the step loop pays only the snapshot,
the writer thread pays the rest.
"""
from __future__ import annotations

import os
import re
import shutil
import sys
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from flax import serialization


class CorruptCheckpointError(RuntimeError):
    """A checkpoint file failed manifest verification or msgpack decoding —
    distinct from a *template mismatch* (``ValueError``), which means the
    file is fine but belongs to a different model."""


def consolidate(tree):
    """Fetch every leaf to host numpy (all-gathering sharded leaves).

    Single-process sharded arrays are fully addressable and fetch directly;
    multi-process shards (some devices belong to other hosts) go through
    ``multihost_utils.process_allgather`` so every host sees the full value.
    """
    def gather(x):
        if isinstance(x, jax.Array) and not getattr(x, "is_fully_addressable", True):
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return x

    gathered = jax.tree_util.tree_map(gather, tree)
    # one batched transfer for everything still on device: device_get
    # pipelines the copies, where per-leaf np.asarray pays one
    # device->host round trip per leaf
    return jax.device_get(gathered)


def _wrap_rng(tree: Dict[str, Any]) -> Dict[str, Any]:
    """PRNG key arrays don't serialize; store key_data (rewrapped in load)."""
    out = dict(tree)
    if "rng" in out:
        out["rng"] = jax.random.key_data(out["rng"])
    return out


def snapshot(tree) -> Any:
    """Device→host copy of a checkpointable tree — the ONLY part of a save
    the step loop must pay.  Collective when the tree holds cross-host
    shards (every process must call it); the returned host tree is plain
    numpy and safe to serialize on any thread."""
    return consolidate(_wrap_rng(tree) if isinstance(tree, dict) else tree)


def manifest_path(path: str) -> str:
    return path + ".manifest.json"


def prev_path(path: str) -> str:
    """Where the previously published snapshot is retained for fallback."""
    return path + ".prev"


def _atomic_write_bytes(path: str, data: bytes) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # atomic: a crash never leaves a torn file


def write_json_atomic(path: str, obj) -> None:
    """Crash-atomic JSON sidecar write (tmp + ``os.replace``) — the same
    no-torn-reads contract as checkpoint publishes, for the small metadata
    files that ride along (``-best.json``, trainer state)."""
    import json

    _atomic_write_bytes(path, json.dumps(obj, indent=2).encode("utf-8"))


def _retain_prev(path: str) -> None:
    """Keep the currently published ``path`` (and its manifest) reachable as
    ``path.prev`` before the new data replaces it.  Hardlink where the
    filesystem allows (free, and ``path`` itself is never absent during the
    publish); copy as the fallback."""
    for src in (path, manifest_path(path)):
        if not os.path.exists(src):
            continue
        dst = prev_path(path) if src == path else manifest_path(prev_path(path))
        tmp = dst + ".tmp"
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
            os.link(src, tmp)
        except OSError:
            shutil.copyfile(src, tmp)
        os.replace(tmp, dst)


def publish(path: str, data: bytes, meta: Optional[Dict] = None) -> None:
    """Crash-atomically publish one checkpoint file + its manifest.

    Order matters: retain the previous snapshot, replace the data, then
    replace the manifest.  A crash at ANY point leaves a loadable state —
    either the old (data+manifest) pair, or new data whose stale manifest
    fails verification and routes :func:`load` to the retained ``.prev``.
    Only a completed publish (new data + matching manifest) supersedes the
    previous snapshot.  ``meta`` (e.g. step / steps-per-epoch at save time)
    is carried in the manifest, not the msgpack payload, so readers can
    inspect it without decoding the full state."""
    import json

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    # retain ONLY a still-verifying pair: after a torn publish (new data,
    # stale manifest) the retained .prev is the one loadable snapshot —
    # overwriting it with the corrupt pair would leave zero on a second
    # crash in the same window
    if os.path.exists(path) and _manifest_matches(path):
        _retain_prev(path)
    _atomic_write_bytes(path, data)
    crc = zlib.crc32(data) & 0xFFFFFFFF
    man = {"version": 1, "file": os.path.basename(path), "bytes": len(data),
           "crc32": crc}
    if meta:
        man["meta"] = dict(meta)
    _atomic_write_bytes(manifest_path(path),
                        json.dumps(man, indent=2).encode("utf-8"))
    _published_crc[path] = (len(data), crc)


def load_manifest(path: str) -> Optional[Dict]:
    """The manifest published alongside ``path``, or None (pre-manifest
    file).  An UNDECODABLE manifest raises ``ValueError`` (json's decode
    error) — the verified readers convert that to
    :class:`CorruptCheckpointError` so a bit-rotted manifest routes to the
    ``.prev`` fallback instead of crashing the caller raw."""
    import json

    try:
        with open(manifest_path(path)) as f:
            return json.load(f)
    except OSError:
        return None


#: (bytes, crc32) of the last pair THIS process published per path — lets
#: the retention guard trust its own completed publishes from the manifest
#: alone instead of re-reading + re-CRCing the full previous state file
#: (hundreds of MB at scale) on every save
_published_crc: Dict[str, Tuple[int, int]] = {}


def _manifest_matches(path: str) -> bool:
    """No-msgpack-decode check that ``path``'s bytes agree with its
    manifest — the retention guard: only a pair that still verifies may
    overwrite the previous ``.prev``.  A legacy file without a manifest
    passes (nothing to disagree with).  When the manifest equals the pair
    this process last published to ``path``, the data file is NOT re-read
    — publish completed, so the bytes on disk are the ones the manifest
    describes; only the first publish of a path (unknown provenance) pays
    the full read + CRC."""
    try:
        man = load_manifest(path)
    except ValueError:
        return False
    if man is None:
        return True
    if not isinstance(man, dict):
        return False
    if _published_crc.get(path) == (man.get("bytes"), man.get("crc32")):
        return True
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return False
    return (man.get("bytes") == len(data)
            and man.get("crc32") == (zlib.crc32(data) & 0xFFFFFFFF))


def discard(path: str) -> None:
    """Remove a snapshot and every artifact the publish protocol leaves
    around it (manifest, retained ``.prev`` + its manifest, stray tmps) —
    the elastic launcher's stale-state cleanup."""
    for p in (path, manifest_path(path), prev_path(path),
              manifest_path(prev_path(path))):
        for q in (p, p + ".tmp"):
            if os.path.exists(q):
                os.remove(q)


def save(path: str, tree, meta: Optional[Dict] = None) -> None:
    """Consolidate + atomically publish (data + checksum manifest).

    EVERY process must call this (consolidate runs a collective all-gather
    for cross-host shards); only process 0 touches the filesystem — the
    rank-0-writes split of ``multi-gpu-distributed-cls.py:192,196-197``
    without its deadlock risk.
    """
    data_tree = snapshot(tree)
    if jax.process_index() != 0:
        return
    publish(path, serialization.to_bytes(data_tree), meta=meta)


def _read_raw_verified(path: str) -> Tuple[Any, Optional[Dict]]:
    """``(raw_tree, manifest_meta)`` after checksum + decode verification.

    Raises :class:`CorruptCheckpointError` when the published manifest does
    not match the bytes on disk or the msgpack payload fails to decode; a
    missing manifest (pre-manifest file) skips the checksum but still
    decode-verifies."""
    with open(path, "rb") as f:
        data = f.read()
    try:
        man = load_manifest(path)
    except ValueError as e:  # bit-rotted/truncated manifest JSON
        raise CorruptCheckpointError(
            f"checkpoint {path!r}: manifest {manifest_path(path)!r} is not "
            f"decodable JSON: {e}") from e
    if man is not None:
        if not isinstance(man, dict) or "crc32" not in man:
            raise CorruptCheckpointError(
                f"checkpoint {path!r}: manifest {manifest_path(path)!r} is "
                "unreadable")
        if man.get("bytes") != len(data) or \
                man.get("crc32") != (zlib.crc32(data) & 0xFFFFFFFF):
            raise CorruptCheckpointError(
                f"checkpoint {path!r} fails manifest verification "
                f"(expected {man.get('bytes')} bytes crc32 "
                f"{man.get('crc32')}, found {len(data)} bytes crc32 "
                f"{zlib.crc32(data) & 0xFFFFFFFF}) — truncated or corrupt "
                "write")
    try:
        raw = serialization.msgpack_restore(data)
    except Exception as e:
        raise CorruptCheckpointError(
            f"checkpoint {path!r} is not decodable msgpack: {e}") from e
    return raw, (man or {}).get("meta")


def read_verified(path: str, *, fallback: bool = True
                  ) -> Tuple[Any, Optional[Dict], str]:
    """Verified raw restore with previous-snapshot fallback:
    ``(raw_tree, manifest_meta, path_actually_read)``.

    A corrupt (or vanished) ``path`` falls back to the retained
    ``path.prev`` with a LOUD warning — resuming from the previous snapshot
    loses at most one snapshot interval, where crashing loses the run."""
    try:
        raw, meta = _read_raw_verified(path)
        return raw, meta, path
    except (CorruptCheckpointError, FileNotFoundError) as e:
        prev = prev_path(path)
        if not (fallback and os.path.exists(prev)):
            raise
        print(f"WARNING: {e} — falling back to the previous published "
              f"snapshot {prev!r}", file=sys.stderr)
        raw, meta = _read_raw_verified(prev)
        return raw, meta, prev


def verify(path: str) -> Tuple[bool, Optional[str]]:
    """``(ok, reason)`` — does ``path`` satisfy the published-snapshot
    contract (manifest checksum + decodable payload)?  Template-free; the
    bench resilience gate and tests use it."""
    try:
        _read_raw_verified(path)
        return True, None
    except FileNotFoundError:
        return False, "missing"
    except CorruptCheckpointError as e:
        return False, str(e)


def load(path: str, like, *, fallback: bool = True) -> Any:
    """Restore a pytree with the structure/dtypes of ``like``.

    Verifies the manifest checksum first and falls back to the retained
    previous snapshot (``read_verified``) on corruption.  Raises
    ``ValueError`` on leaf-shape mismatch — flax ``from_bytes`` does not
    validate shapes, which would defer the failure to an opaque XLA error
    at the next forward pass (e.g. loading a ``bert-tiny`` checkpoint into
    a ``bert-base`` template).  A shape mismatch is NOT corruption and
    never falls back.
    """
    raw, _meta, used = read_verified(path, fallback=fallback)
    return from_restored(raw, like, path=used)


def from_restored(raw, like, *, path: str = "<restored>") -> Any:
    """:func:`load`'s template fit + shape validation applied to an
    already-restored raw tree (:func:`load_raw`'s output) — consumers that
    must inspect the raw tree first (the serve engine probes for int8
    ``qscale`` leaves) pay ONE file read + msgpack decode, not two.
    ``path`` only labels error messages."""
    template = _wrap_rng(like) if isinstance(like, dict) and "rng" in like else like
    restored = serialization.from_state_dict(template, raw)
    got_leaves = jax.tree_util.tree_leaves(restored)
    want = jax.tree_util.tree_leaves_with_path(template)
    got_shapes = [getattr(l, "shape", None) for l in got_leaves]
    want_shapes = [getattr(l, "shape", None) for _, l in want]
    if got_shapes != want_shapes:
        (keypath, _), bad_got, bad_want = next(
            (w, g, ws) for w, g, ws in zip(want, got_shapes, want_shapes)
            if g != ws)
        leaf = jax.tree_util.keystr(keypath)
        # a [2]u32-vs-[4]u32 *rng* leaf means the checkpoint was saved under
        # a different PRNG impl (threefry2x32 vs rbg), not a different model
        if "rng" in leaf and {bad_got, bad_want} <= {(2,), (4,)}:
            raise ValueError(
                f"checkpoint {path!r} stores an RNG key of a different PRNG "
                f"impl than the current --rng_impl (key_data {bad_got} vs "
                f"{bad_want}: threefry2x32 is [2]u32, rbg is [4]u32) — rerun "
                "with the --rng_impl it was saved under")
        raise ValueError(
            f"checkpoint {path!r} does not match the model template: "
            f"leaf {leaf} has shape {bad_got} vs expected {bad_want}")
    if isinstance(restored, dict) and "rng" in restored and isinstance(like, dict):
        restored = dict(restored)
        # rewrap with the template key's impl (rbg key_data is [4]u32,
        # threefry [2]u32 — default wrap would mis-type an rbg stream)
        restored["rng"] = jax.random.wrap_key_data(
            restored["rng"], impl=jax.random.key_impl(like["rng"]))
    return restored


def load_raw(path: str) -> Any:
    """Template-free restore: the checkpoint's raw pytree as host numpy.

    The read-only half of :func:`load` for consumers that have no model
    template yet — the serving engine peeks a checkpoint's leaf shapes to
    fail fast on a model mismatch BEFORE paying device transfer, and the
    ``serve_tpu.py`` CLI prints what a file contains.  Manifest-verified
    like :func:`load` but WITHOUT the ``.prev`` fallback — a template-free
    consumer must decide for itself whether an older snapshot is an
    acceptable substitute.  Never use this to feed a forward pass directly;
    :func:`load` (shape-validated against the model template) is the
    loading path.
    """
    raw, _meta = _read_raw_verified(path)
    return raw


def save_params(path: str, state: Dict[str, Any],
                meta: Optional[Dict] = None) -> None:
    """Model-only checkpoint — the ``state_dict`` analog used by test/predict."""
    save(path, state["params"], meta=meta)


def load_params(path: str, like_params) -> Any:
    return load(path, like_params)


def save_state(path: str, state: Dict[str, Any],
               meta: Optional[Dict] = None) -> None:
    """Full resume checkpoint: params + opt_state + step + rng.  ``meta``
    (step / steps-per-epoch at save time) rides the manifest — the
    elastic-width resume reads it to remap the data position onto a
    different data-parallel mesh width."""
    save(path, state, meta=meta)


def load_state(path: str, like_state: Dict[str, Any]) -> Dict[str, Any]:
    return load(path, like_state)


_STEP_RE = re.compile(r"[-_.](\d+)$")


def _filename_step(path: str, pattern: str) -> Optional[tuple]:
    """``(stem, step)`` for a step-family checkpoint name — a TRAILING
    integer set off by ``-``/``_``/``.`` right before the suffix
    (``ckpt-1500.msgpack`` -> ``("ckpt", 1500)``) — or None.  Interior or
    attached digits are NOT steps: ``zero2-cls`` and ``pretrained-e5``
    name a strategy and an epoch tag, not a step counter."""
    base = os.path.basename(path)
    if base.endswith(pattern):
        base = base[:len(base) - len(pattern)]
    m = _STEP_RE.search(base)
    return (base[:m.start()], int(m.group(1))) if m else None


def latest(output_dir: str, pattern: str = ".msgpack") -> Optional[str]:
    """Newest checkpoint in a directory, or None.

    mtime alone is the wrong order key twice over: coarse-mtime
    filesystems tie checkpoints written within the same second, and a
    ``cp -p`` restore resurrects old timestamps wholesale — after which
    "newest mtime" silently serves a stale file.  When every candidate
    belongs to ONE step family (same stem, trailing ``-<step>`` before
    the suffix), the step ORDERS them (mtime only breaks step ties);
    any mixed-family directory falls back to mtime with deterministic
    name tie-breaks, so `pretrained-e5.msgpack` can never outrank a
    newer `zero2-cls.msgpack` on its epoch digit.

    Deliberate consequence: within one family the highest STEP wins even
    when a lower-step file is newer on disk — a reused output_dir whose
    new run restarts the step counter should be cleaned (or given a new
    dir) first, the same contract resume already has.
    """
    if not os.path.isdir(output_dir):
        return None
    cands = [os.path.join(output_dir, f) for f in os.listdir(output_dir)
             if f.endswith(pattern)]
    if not cands:
        return None
    steps = {c: _filename_step(c, pattern) for c in cands}
    if all(s is not None for s in steps.values()) \
            and len({s[0] for s in steps.values()}) == 1:
        return max(cands, key=lambda c: (steps[c][1], os.path.getmtime(c)))
    return max(cands, key=lambda c: (os.path.getmtime(c),
                                     steps[c][1] if steps[c] else -1,
                                     os.path.basename(c)))
