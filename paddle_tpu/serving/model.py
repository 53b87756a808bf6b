"""What the decode engine asks of a model: a *model description*.

``DecodeEngine``'s paged prefill and decode programs are ``embed -> layers
-> final norm -> head`` with the caches handed in and out as the loop's
carry; everything between embedding and logits is the model's. A
description is any object with:

- ``cfg`` (its ``dtype`` is the compute dtype), ``vocab_size``,
  ``max_positions`` (``None`` where no positional table bounds ``max_seq``);
- ``cache_pools``: ``{"layers": n, "rows": (shape, ...)}``: the page pools
  it asks of ``PagedKVCache``, one ``[layers, pages, page, *shape]`` a
  row shape, the attention layers alone: ``((heads * head_dim,),) * 2``
  for keys and values (a token's heads flat in the lanes, what the
  page-table kernel reads; with ``"heads": (heads, head_dim)`` where page
  contents travel as heads: prefix store, KV hand-off), ``((width,),)``
  for one pool of latent rows (then ``latent`` is true); or, where its
  layers keep different spans of the context,
  ``{"groups": [{"name", "layers", "rows", "window"}, ...]}``:
  several page groups under the one manager, each with its pools, free
  list and a table a slot, a group with a ``window`` bounded by it
  (``serving/paged_kv.py``); the programs are then handed the groups'
  table rows side by side and ``ctx.table_widths`` says where each
  starts;
- ``recurrent`` and ``state_geometry``: whether slots carry recurrent
  state beside their pages, and the shapes of a slot's two state rows a
  recurrent layer as the model states them, ``{"layers": n, "conv":
  shape, "ssm": shape}`` (``PagedKVCache``'s ``state``: the conv's last
  inputs in the cache's dtype, the recurrence's state float32, be it a
  scan's ``[d_state, d_inner]`` or a delta rule's matrix a head);
  optionally ``delta_chunks(tokens)``: the chunks a prompt costs a
  chunked recurrence (``serve/prefill`` records it);
- ``paged_kernel``: whether ``decode_layers`` can read the pools through
  a page-table kernel (``kv_path`` ``pallas_paged``), or always gathers;
  and where it can, ``kernel_takes_pages(page_size, cache_dtype)``:
  whether Mosaic takes this engine's page shape;
- ``hold(params, weight_dtype, chunk, sharded=False)``: the serving
  storage of a float32 parameter tree, a leaf re-laid where the programs
  contract it better so (``sharded``: the engine lays the tree over a mesh
  by the stored layout's plan, so every leaf keeps its stored shape);
- ``embed(qparams, tokens, positions)``;
- ``prefill_layers(qparams, x [1, T, D], caches, ctx)`` with ``ctx``:
  ``length``, ``prefix_len``, ``table_row``, ``slot``, ``page_size``,
  ``table_widths``;
- ``decode_layers(qparams, x [B, D], caches, ctx)`` with ``ctx``:
  ``positions``, ``tables``, ``actives``, ``page_size``, ``kv_path``,
  ``fused``, ``table_widths``; both return ``(x, caches)``, the caches a
  tuple ``(k pool, v pool[, conv, ssm])`` (or ``(latent pool,)``; with
  page groups a pool pair a group) updated in place, and a
  model with experts a third value, its layers' report (one small int32
  array that the engine hands out with the logits);
- ``logits(qparams, h, fused=False)``: final norm and head, float32;
- ``forward(params, tokens [1, T])``: the plain full forward pass (the
  engine's parity surface);
- optionally ``verify_layers(qparams, x [B, W, D], caches, ctx)`` with
  ``ctx``: ``starts``, ``positions`` ``[B, W]``, ``tables``,
  ``page_size``: the speculative-verify window's layers, returning ``(x,
  caches)``; ``logits`` then takes ``[B, W, D]``. An engine whose
  description has none is refused ``verify_window > 0``.

A config names its own description: ``cfg.serving_description()``. Six
exist, each beside its model: ``models/gpt_serving.py:GPTServing``
(``models/gpt.py``'s block; the one with ``verify_layers``),
``models/jamba.py:JambaServing``, ``models/kimi_k2.py:KimiK2Serving``,
``models/olmo_hybrid.py:OlmoHybridServing``,
``models/cohere2_moe.py:Cohere2MoeServing`` (window and global layers: two
page groups) and ``models/solar_open2.py:SolarOpen2Serving`` (the first
that is ``recurrent`` AND has experts: caches ``(k pool, v pool, conv,
ssm)`` and the report together). This package imports none of them: a new
family adds its file under ``models/`` and edits nothing here.
"""
from __future__ import annotations

import types

__all__ = ["describe", "ctx"]


def describe(cfg):
    """A model description from what ``DecodeEngine`` was given: one as it
    is, a config through the description it names."""
    if hasattr(cfg, "prefill_layers"):
        return cfg
    if hasattr(cfg, "serving_description"):
        return cfg.serving_description()
    raise TypeError(
        f"DecodeEngine: no model description for {type(cfg).__name__}; "
        "pass an object with the surface serving/model.py lists")


def ctx(**kw):
    """The per-call context a program hands its model's layers."""
    return types.SimpleNamespace(**kw)
