"""Golden-trace regression test — the TPU analog of the reference's
published first-5-step loss sequences (``/root/reference/README.md:29-34``,
same-seed reproducible traces as the de-facto regression suite).

The fixture freezes a seeded 30-step mesh-DP loss trace (dropout ON, so the
RNG plumbing is pinned too).  Any change to init, data order, masking,
dropout streams, loss math, or the optimizer shifts these numbers; a
refactor that is truly behavior-preserving does not.  Regenerate the asset
ONLY for deliberate, documented training-math changes.
"""
import json
import os

import numpy as np
import pytest

from pdnlp_tpu.train.run import build_parallel_trainer
from pdnlp_tpu.utils.config import Args

from pdnlp_tpu.utils.config import _DEFAULT_DATA

# the assets hold traces recorded on the REAL corpus: without it there is
# nothing to compare against (a synthetic corpus gives other numbers by
# construction), so the module skips and says why
pytestmark = pytest.mark.skipif(
    not os.path.exists(_DEFAULT_DATA),
    reason=f"golden traces were recorded on {_DEFAULT_DATA}, which is not "
           "on this machine")

ASSET = os.path.join(os.path.dirname(__file__), "assets", "golden_trace.json")
MODES_ASSET = os.path.join(os.path.dirname(__file__), "assets",
                           "golden_modes.json")


def test_golden_loss_trace(ndev):
    with open(ASSET) as f:
        golden = json.load(f)
    c = golden["config"]
    assert ndev == 8, "trace was recorded on the 8-device CPU mesh"
    args = Args(model=c["model"], max_seq_len=c["max_seq_len"],
                train_batch_size=c["train_batch_size"],
                data_limit=c["data_limit"], dtype=c["dtype"], seed=c["seed"],
                rng_impl=c.get("rng_impl", "threefry2x32"),
                log_every=10 ** 9)
    trainer, loader, _ = build_parallel_trainer(args, mode="dp")
    losses, epoch = [], 0
    while len(losses) < c["steps"]:
        loader.set_epoch(epoch)
        for b in loader:
            trainer.state, m = trainer.train_step(trainer.state, trainer.put(b))
            losses.append(float(m["loss"]))
            if len(losses) == c["steps"]:
                break
        epoch += 1
    np.testing.assert_allclose(losses, golden["losses"], rtol=1e-5, atol=1e-6)


def _modes_golden():
    with open(MODES_ASSET) as f:
        return json.load(f)


from tests.golden_modes import MODES


@pytest.mark.parametrize("mode", list(MODES))
def test_golden_mode_traces(mode, ndev):
    """10-step loss trace per SHARDING PATH (zero/tp/pp/sp/ep/shardmap next
    to dp): a refactor of any path that silently changes its math shifts its
    trace.  Same contract as the 30-step dp golden; regenerate with
    scripts/regen_golden.py only for deliberate training-math changes."""
    assert ndev == 8, "traces were recorded on the 8-device CPU mesh"
    from tests.golden_modes import trace

    golden = _modes_golden()[mode]
    got = trace(mode, golden["steps"])
    np.testing.assert_allclose(got, golden["losses"], rtol=1e-5, atol=1e-6)
