"""R16 — KV cache rebuilt by concatenation inside a decode loop.

The generative decode hot path lives or dies on two properties the serve
engine gets by construction (``pdnlp_tpu.serve.decode``): the KV cache is
PREALLOCATED (``[L, n_pages, page_sz, H]``, donated across steps — decode
never allocates HBM) and the decode step has ONE fixed shape (``[rows,
1]`` — retrace-free after warmup).  The textbook anti-pattern breaks both
at once::

    for _ in range(max_new):
        logits, k_new, v_new = decode_step(params, tok, k_cache, v_cache)
        k_cache = jnp.concatenate([k_cache, k_new], axis=2)   # <- R16

Every token reallocates the whole cache (O(T²) bytes moved over a
generation) and, under jit, the growing shape retraces the step on every
single token — the decode analog of the R7/R9 step-loop stalls.

The paged cache (``pdnlp_tpu.serve.kvpage``) has its own spelling of the
same bug: the per-stream page TABLE rebuilt by concatenate as pages are
claimed, or the page arrays re-stacked per token::

    for _ in range(max_new):
        logits, new_page = paged_decode_step(tok, pages_k, page_table)
        page_table = jnp.concatenate([page_table, new_page])       # <- R16
        pages_k = jnp.stack([pages_k, fresh_pages])                # <- R16

Same two losses: the table/pool reallocates per token, and the growing
extent retraces the one decode program paging exists to keep fixed.  The
engine's fix is structural — the table is a fixed ``[slots,
pages_per_stream]`` host array updated in place at attach/detach, and the
page pool is preallocated and donated.

Heuristic, per lexical ``for``/``while`` loop (R7/R9's loop-body
machinery): the loop is DECODE-SHAPED — it dispatches a call whose name's
last segment contains ``decode``/``prefill``/``generate`` or matches the
jitted-step convention (``*step``/``*step_fn``) — and the body calls an
array-concatenation builder (``concatenate``/``append``/``stack``/
``hstack``/``vstack``, by import resolution or last-segment name) with any
argument that names KV state (an identifier matching ``kv``/``cache``/
``past``/``page``, case-insensitive — the last covers ``page_table`` /
``pages_k`` / ``pages_v`` — incl. inside list/tuple literals).  The
finding lands on the concatenate call.

``.at[...].set(...)`` and ``lax.dynamic_update_slice`` — the fix — never
match; neither does concatenation of non-cache values in a decode loop,
nor a one-time cache/table assembly outside any decode loop.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator

from pdnlp_tpu.analysis.core import (
    Finding, ModuleInfo, Rule, dotted_name, is_step_call, loop_body_calls,
    register,
)

_REBUILD_NAMES = {"concatenate", "append", "stack", "hstack", "vstack",
                  "dstack", "column_stack"}
_REBUILD_RESOLVED = {f"jax.numpy.{n}" for n in _REBUILD_NAMES} \
    | {f"numpy.{n}" for n in _REBUILD_NAMES}
_DECODE_CALL_RE = re.compile(r"(decode|prefill|generate)", re.I)
_CACHE_NAME_RE = re.compile(r"(kv|cache|past|page)", re.I)


@register
class KVCacheReallocInDecodeLoop(Rule):
    rule_id = "R16"
    name = "kv-cache-realloc-in-decode-loop"
    hint = ("preallocate the KV storage once ([slots, max_len] positions, "
            "or a paged pool with a fixed [slots, pages_per_stream] page "
            "table updated in place) and write new K/V with "
            "cache.at[rows, pos].set(...) or lax.dynamic_update_slice "
            "into a DONATED buffer (pdnlp_tpu.serve.decode."
            "PagedDecodeEngine is the engine form) — a concatenate "
            "rebuild reallocates the whole cache or table every token "
            "and the growing shape retraces the jitted step per "
            "generated token")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not self._relevant(mod):
            return
        for loop in ast.walk(mod.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            calls = loop_body_calls(mod, loop)
            if not any(self._is_decode_dispatch(c) for c in calls):
                continue
            for c in calls:
                if self._is_rebuild(mod, c) and self._names_cache(c):
                    yield self.finding(
                        mod, c,
                        "KV cache rebuilt by concatenation inside a "
                        "decode loop — every generated token reallocates "
                        "the whole cache and the growing shape retraces "
                        "the step, instead of one dynamic update into a "
                        "donated preallocated buffer")

    @staticmethod
    def _relevant(mod: ModuleInfo) -> bool:
        return "jax" in mod.aliases or any(
            a.startswith("jax") for a in mod.aliases.values())

    @staticmethod
    def _is_decode_dispatch(call: ast.Call) -> bool:
        name = dotted_name(call.func)
        if not name:
            return False
        last = name.split(".")[-1]
        return bool(_DECODE_CALL_RE.search(last)) or is_step_call(call)

    def _is_rebuild(self, mod: ModuleInfo, call: ast.Call) -> bool:
        if mod.resolves_to(call.func, _REBUILD_RESOLVED):
            return True
        name = dotted_name(call.func)
        if not name:
            return False
        return name.split(".")[-1] in _REBUILD_NAMES

    @staticmethod
    def _names_cache(call: ast.Call) -> bool:
        """Any argument (incl. elements of list/tuple literals) that is a
        Name/Attribute whose last segment reads like KV state."""
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            for node in ast.walk(arg):
                ident = None
                if isinstance(node, ast.Name):
                    ident = node.id
                elif isinstance(node, ast.Attribute):
                    ident = node.attr
                if ident and _CACHE_NAME_RE.search(ident):
                    return True
        return False
