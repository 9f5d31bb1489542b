"""RTP020: the KV handoff plane never materializes pool KV as a blob.

Disaggregated prefill/decode moves KV pages between replicas as chunk
reads sliced from per-page host views (source) written into a
final-size staging region (sink) — the r11 receive discipline applied
to KV. The pool itself can be sharded across a tensor-parallel mesh,
which raises the stakes: one careless whole-pool ``np.asarray`` or
``.tobytes()`` doesn't just double host memory, it device-gathers
every shard of every page through one host hop. Like RTP014's blob
rule for the object plane, each violation is a single innocent-looking
line.

Flagged in the KV shipping seams (disagg, prefix router, serving):

- ``.tobytes()`` calls (ndarray flatten-to-heap) and zero-argument
  ``.to_bytes()`` (``int.to_bytes(4, "little")`` is framing — not
  flagged);
- whole-pool gathers: ``asarray``/``ascontiguousarray``/``array``/
  ``device_get`` applied to a bare ``<x>.k``/``<x>.v`` pool attribute
  (or an export's ``<x>.held_k``/``<x>.held_v``, its own copy of the
  pages it pinned) or to a single subscript of one (``cache.k[li]`` is
  a full layer, ``[num_pages, page_size, kv_heads * head_dim]``; page
  reads subscript twice);
- ``join`` on a ``bytes``/``bytearray`` literal or constructor
  (assembling a stream on the heap instead of staging at offset);
- ``pickle.dumps`` / ``cloudpickle.dumps`` (KV never rides pickle).

Sanctioned sites carry the reason inline on the call line::

    # kv-ship-ok: <why materializing here is correct>
"""

from __future__ import annotations

import ast

from raytpu.analysis.core import Rule, register

_SANCTION = "kv-ship-ok:"

_GATHERERS = ("asarray", "ascontiguousarray", "array", "device_get")
_POOL_ATTRS = ("k", "v", "held_k", "held_v")


def _line_sanctioned(mod, lineno: int) -> bool:
    try:
        return _SANCTION in mod.lines[lineno - 1]
    except IndexError:
        return False


def _is_bytes_joiner(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant) and isinstance(node.value,
                                                    (bytes, bytearray)):
        return True
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("bytes", "bytearray"))


def _is_pool_ref(node: ast.expr) -> bool:
    """``<x>.k`` / ``<x>.v`` (the whole pool list; likewise an export's
    ``held_k`` / ``held_v``) or one subscript of it (``cache.k[li]``:
    every page of a layer). Two subscripts deep is a single page,
    ``[page_size, kv_heads * head_dim]`` — the sanctioned streaming
    grain."""
    if isinstance(node, ast.Attribute) and node.attr in _POOL_ATTRS:
        return True
    return (isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Attribute)
            and node.value.attr in _POOL_ATTRS)


@register
class KVShipping(Rule):
    id = "RTP020"
    name = "no-materialized-KV-shipping"
    invariant = ("KV handoff seams never flatten pool KV — no "
                 ".tobytes()/zero-arg .to_bytes(), no whole-pool or "
                 "whole-layer host gathers, no bytes-join stream "
                 "assembly, no pickle.dumps; sanctioned sites carry "
                 "'# kv-ship-ok: <reason>'")
    rationale = ("a materialized KV blob doubles host memory and, on a "
                 "tensor-parallel pool, device-gathers every shard "
                 "through one host hop — the exact costs the paged "
                 "streaming handoff exists to avoid")
    scope = ("raytpu/inference/disagg.py",
             "raytpu/inference/serving.py",
             "raytpu/serve/_private/prefix_router.py")

    def check(self, mod):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            msg = None
            if (isinstance(f, ast.Attribute) and f.attr == "tobytes"):
                msg = ("ndarray .tobytes() flattens KV onto the heap — "
                       "serve memoryview slices of per-page views, or "
                       "sanction with '# kv-ship-ok: <reason>'")
            elif (isinstance(f, ast.Attribute) and f.attr == "to_bytes"
                    and not node.args and not node.keywords):
                msg = ("zero-arg .to_bytes() materializes the whole "
                       "object — stream page-granular chunks, or "
                       "sanction with '# kv-ship-ok: <reason>'")
            elif (isinstance(f, ast.Attribute) and f.attr in _GATHERERS
                    and node.args and _is_pool_ref(node.args[0])):
                msg = ("whole-pool/whole-layer host gather of the KV "
                       "pool — read one page per view (subscript to "
                       "page granularity), or sanction with "
                       "'# kv-ship-ok: <reason>'")
            elif (isinstance(f, ast.Attribute) and f.attr == "join"
                    and _is_bytes_joiner(f.value)):
                msg = ("bytes join assembles the KV stream on the heap "
                       "— stage chunks at their wire offset in a "
                       "final-size region, or sanction with "
                       "'# kv-ship-ok: <reason>'")
            elif (isinstance(f, ast.Attribute) and f.attr == "dumps"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in ("pickle", "cloudpickle")):
                msg = ("whole-value pickle.dumps on the KV shipping "
                       "path — KV rides raw page bytes, or sanction "
                       "with '# kv-ship-ok: <reason>'")
            if msg is None or _line_sanctioned(mod, node.lineno):
                continue
            yield self.finding(mod, node, msg)
