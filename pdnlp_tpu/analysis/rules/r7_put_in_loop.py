"""R7 — per-step host->device uploads inside a step loop.

A ``device_put``/``put(batch)`` issued in the same loop that dispatches a
jitted step pays host->device transport EVERY iteration, serializing the
upload against dispatch — the transport tax the input-pipeline
subsystem (``pdnlp_tpu.data.pipeline``) exists to eliminate: hold the
encoded split resident in HBM (zero steady-state bytes per step) or
double-buffer the upload so it overlaps the previous step's execution.

Heuristic, per lexical ``for``/``while`` loop: the loop body contains BOTH

- an upload call — ``jax.device_put`` / ``jax.device_put_sharded`` /
  ``jax.make_array_from_process_local_data``, or a method/function whose
  name is exactly ``put``/``put_fused`` (``self.put(batch)``, the repo's
  strategy-upload convention).  Queue puts are exempted by receiver name
  (``q``/``queue``-ish) — ``q.put(item)`` is host plumbing, not transport;
- a step dispatch — a call whose name's last segment ends in ``step`` or
  ``step_fn`` (``train_step``, ``self.multi_step``, ``step``), the repo's
  jitted-step naming convention (R5 polices it stays meaningful).

Comprehensions are NOT loops here: ``[put(b) for b in loader]`` staged
before a separate dispatch pass (the eval-cache idiom) is the fix, not the
hazard.  The finding lands on the upload call.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator

from pdnlp_tpu.analysis.core import (
    Finding, ModuleInfo, Rule, dotted_name, is_step_call, loop_body_calls,
    register,
)

_PUT_FUNCS = {
    "jax.device_put", "jax.device_put_sharded", "jax.device_put_replicated",
    "jax.make_array_from_process_local_data",
}
_PUT_NAME_RE = re.compile(r"^put(_fused)?$")
_QUEUE_RECV_RE = re.compile(r"^(q|queue|.*_q|.*queue)$", re.IGNORECASE)


@register
class PutInStepLoop(Rule):
    rule_id = "R7"
    name = "device-put-in-step-loop"
    hint = ("move the upload out of the step loop: route batches through "
            "pdnlp_tpu.data.pipeline (device-resident split = zero "
            "steady-state transport; DevicePrefetch = the put for batch "
            "k+1 overlaps step k)")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if "jax" not in mod.aliases and not any(
                a.startswith("jax") for a in mod.aliases.values()):
            return  # pure-host module: its puts are not device transport
        for loop in ast.walk(mod.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            calls = loop_body_calls(mod, loop)
            if not any(is_step_call(c) for c in calls):
                continue
            for c in calls:
                if self._is_put_call(mod, c):
                    yield self.finding(
                        mod, c,
                        "host->device upload inside a loop that dispatches "
                        "a jitted step — every iteration pays transport "
                        "serially with dispatch")

    def _is_put_call(self, mod: ModuleInfo, call: ast.Call) -> bool:
        if mod.resolves_to(call.func, _PUT_FUNCS):
            return True
        name = dotted_name(call.func)
        if not name:
            return False
        parts = name.split(".")
        if not _PUT_NAME_RE.fullmatch(parts[-1]):
            return False
        # q.put(item) / out_queue.put(x): host plumbing, not transport
        if len(parts) > 1 and _QUEUE_RECV_RE.fullmatch(parts[-2]):
            return False
        return True
