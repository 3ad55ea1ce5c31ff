"""Model assembly: init / train-forward / prefill / decode over segments.

A model is a list of segments ``(pattern, repeats)``; parameters of each
segment are stacked ``[R, ...]`` and executed with ``lax.scan`` over repeats
(pattern slots unrolled in the body), so HLO size scales with the pattern
length, not the layer count.  ``jax.checkpoint`` (remat) wraps the scan body
when ``cfg.remat``.

Serving has two decode data paths: the dense per-row cache
(``init_cache``/``decode_step``) and the fully-paged path
(``decode_step_paged``) where every attention layer reads and writes
shared KV page pools through ``kernels.paged_attention`` -- see
docs/serving.md.  ``prefill_batched`` packs a scheduler step's admissions
into one right-padded forward pass for either path.

All functions are pure; sharding is applied externally (pjit in_shardings
from the spec tree + optional ``shard_fn`` activation constraints).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models.config import LayerKind, ModelConfig, parse_kind

Params = Dict[str, Any]
_IDENT = lambda x, names: x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _slot_init(key, cfg: ModelConfig, kind: LayerKind):
    """(params, specs) for one layer slot."""
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    p["norm1"], s["norm1"] = L.rms_norm_init(cfg.d_model)
    if kind.is_attention:
        init = L.mla_init if kind.mla else L.attention_init
        p["attn"], s["attn"] = init(ks[0], cfg)
    elif kind.base == "mlstm":
        p["cell"], s["cell"] = R.mlstm_init(ks[0], cfg)
    elif kind.base == "slstm":
        p["cell"], s["cell"] = R.slstm_init(ks[0], cfg)
    elif kind.base == "rglru":
        p["cell"], s["cell"] = R.rglru_init(ks[0], cfg)
    if kind.xattn:
        p["norm_x"], s["norm_x"] = L.rms_norm_init(cfg.d_model)
        p["xattn"], s["xattn"] = L.attention_init(ks[1], cfg, cross=True)
    if kind.moe:
        p["norm2"], s["norm2"] = L.rms_norm_init(cfg.d_model)
        p["moe"], s["moe"] = M.moe_init(ks[2], cfg)
    elif cfg.d_ff > 0:
        p["norm2"], s["norm2"] = L.rms_norm_init(cfg.d_model)
        p["mlp"], s["mlp"] = L.mlp_init(ks[2], cfg)
    return p, s


def init(key, cfg: ModelConfig) -> Tuple[Params, Params]:
    """Returns (params, specs).  Segment params stacked [R, ...] with a
    leading "layers" spec axis (always unsharded)."""
    ks = jax.random.split(key, len(cfg.segments) + 2)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = L.embedding_init(ks[0], cfg)
    params["final_norm"], specs["final_norm"] = L.rms_norm_init(cfg.d_model)
    segs_p, segs_s = [], []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        slot_ps, slot_ss = [], []
        for j, kind_s in enumerate(pattern):
            kind = parse_kind(kind_s)
            kseed = jax.random.fold_in(ks[si + 2], j)

            slot_specs = []

            def one(k):
                # the spec tree is plain Python: read it off the traced
                # init rather than allocating another layer to get it
                p, s = _slot_init(k, cfg, kind)
                slot_specs.append(s)
                return p

            stacked = jax.vmap(one)(jax.random.split(kseed, repeats))
            spec = jax.tree.map(
                lambda ax: ("layers",) + tuple(ax) if isinstance(ax, tuple)
                else ax, slot_specs[0],
                is_leaf=lambda x: isinstance(x, tuple) or x is None)
            slot_ps.append(stacked)
            slot_ss.append(spec)
        segs_p.append(slot_ps)
        segs_s.append(slot_ss)
    params["segments"] = segs_p
    specs["segments"] = segs_s
    return params, specs


# ---------------------------------------------------------------------------
# block application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------


def _apply_block_seq(slot_p, cfg: ModelConfig, kind: LayerKind, x, *,
                     positions, cond, mesh, state=None, past=None,
                     k_positions=None, tok_valid=None, shard=_IDENT):
    """Sequence-mode block (train/prefill).  Returns (x, cache_entry, aux).

    ``past`` / ``k_positions`` serve chunked prefill: ``past`` is the
    slot's accumulated cache entries from previous chunks (one repeat's
    slice) and ``k_positions`` the concatenated past++own key positions
    the causal mask must range over.  ``tok_valid`` [B,S] marks the
    tokens an expert layer routes (None: all); an attention slot's entry
    then carries the layer's expert counts under ``moe``."""
    aux = jnp.float32(0.0)
    h = L.rms_norm(x, slot_p["norm1"])
    cache_entry = None
    if kind.is_attention:
        window = cfg.window_size if kind.base == "local" else 0
        kpos = positions if k_positions is None else k_positions
        mask = L.causal_mask(positions, kpos, window=window,
                             prefix_len=cfg.prefix_len)
        if kind.mla:
            out, (ckv, krope) = L.mla_apply(
                slot_p["attn"], cfg, h, positions, mask,
                past=(None if past is None
                      else (past["ckv"], past["krope"])))
            cache_entry = {"ckv": ckv, "krope": krope}
        else:
            out, (k, v) = L.attention_apply(
                slot_p["attn"], cfg, h, h, positions, mask,
                past=(None if past is None else (past["k"], past["v"])))
            cache_entry = {"k": k, "v": v}
        x = x + out
    else:
        apply = {"mlstm": R.mlstm_apply, "slstm": R.slstm_apply,
                 "rglru": R.rglru_apply}[kind.base]
        out, new_state = apply(slot_p["cell"], cfg, h, state)
        cache_entry = new_state
        x = x + out
    x = shard(x, ("batch", "seq", "embed"))
    if kind.xattn and cond is not None:
        hx = L.rms_norm(x, slot_p["norm_x"])
        cpos = jnp.arange(cond.shape[1])[None]
        cmask = jnp.ones((1, hx.shape[1], cond.shape[1]), bool)
        out, _ = L.attention_apply(slot_p["xattn"], cfg, hx, cond,
                                   positions, cmask, kv_positions=cpos,
                                   use_rope=False)
        x = x + out
    if kind.moe:
        h2 = L.rms_norm(x, slot_p["norm2"])
        out, aux, counts = M.moe_apply(slot_p["moe"], cfg, h2, mesh,
                                       valid=tok_valid)
        if kind.is_attention:
            cache_entry["moe"] = counts
        x = x + out
    elif cfg.d_ff > 0 and "mlp" in slot_p:
        h2 = L.rms_norm(x, slot_p["norm2"])
        x = x + L.mlp_apply(slot_p["mlp"], cfg, h2)
    x = shard(x, ("batch", "seq", "embed"))
    return x, cache_entry, aux


def _apply_block_decode(slot_p, cfg: ModelConfig, kind: LayerKind, x, cache,
                        *, cur_pos, cond, mesh=None, shard=_IDENT):
    """One-token decode.  cache: this slot's cache for one repeat.
    Returns (x, new_cache)."""
    h = L.rms_norm(x, slot_p["norm1"])
    b = x.shape[0]
    if kind.is_attention:
        window = cfg.window_size if kind.base == "local" else 0
        if kind.mla:
            out, c_new, kr_new = L.mla_decode(
                slot_p["attn"], cfg, h, cache["ckv"], cache["krope"],
                cache["pos"], cur_pos)
            wslot = _write_slot(cache["pos"], cur_pos, window)
            new_cache = {
                "ckv": _scatter(cache["ckv"], wslot, c_new[:, 0]),
                "krope": _scatter(cache["krope"], wslot, kr_new[:, 0]),
                "pos": _scatter(cache["pos"], wslot, cur_pos),
            }
        else:
            out, k_new, v_new = L.attention_decode(
                slot_p["attn"], cfg, h, cache["k"], cache["v"], cache["pos"],
                cur_pos, window=window)
            wslot = _write_slot(cache["pos"], cur_pos, window)
            new_cache = {
                "k": _scatter(cache["k"], wslot, k_new[:, 0]),
                "v": _scatter(cache["v"], wslot, v_new[:, 0]),
                "pos": _scatter(cache["pos"], wslot, cur_pos),
            }
        x = x + out
    else:
        step = {"mlstm": R.mlstm_step, "slstm": R.slstm_step,
                "rglru": R.rglru_step}[kind.base]
        out, new_cache = step(slot_p["cell"], cfg, h, cache)
        x = x + out
    if kind.xattn and cond is not None:
        hx = L.rms_norm(x, slot_p["norm_x"])
        cpos = jnp.arange(cond.shape[1])[None]
        cmask = jnp.ones((1, 1, cond.shape[1]), bool)
        out, _ = L.attention_apply(slot_p["xattn"], cfg, hx, cond,
                                   cur_pos[:, None], cmask, kv_positions=cpos,
                                   use_rope=False)
        x = x + out
    if kind.moe:
        h2 = L.rms_norm(x, slot_p["norm2"])
        out, _, _ = M.moe_apply(slot_p["moe"], cfg, h2, mesh)
        x = x + out
    elif cfg.d_ff > 0 and "mlp" in slot_p:
        h2 = L.rms_norm(x, slot_p["norm2"])
        x = x + L.mlp_apply(slot_p["mlp"], cfg, h2)
    x = shard(x, ("batch", "seq", "embed"))
    return x, new_cache


def _write_slot(cache_pos, cur_pos, window: int):
    """Cache slot to write: pos for full caches, ring slot for windows."""
    t = cache_pos.shape[1]
    if window > 0 and t == window:
        return cur_pos % window
    return jnp.minimum(cur_pos, t - 1)


def _scatter(cache, slot, entry):
    """cache: [B,T,...]; slot: [B]; entry: [B,...]."""
    b = cache.shape[0]
    return cache.at[jnp.arange(b), slot].set(entry.astype(cache.dtype))


# ---------------------------------------------------------------------------
# segment runners
# ---------------------------------------------------------------------------


def _strip_layers(spec_tree):
    is_axes = lambda t: t is None or (isinstance(t, tuple) and all(
        isinstance(a, (str, type(None))) for a in t))
    return jax.tree.map(
        lambda ax: tuple(ax[1:]) if isinstance(ax, tuple) else ax,
        spec_tree, is_leaf=is_axes)


def _constrain_slots(slot_ps, slot_specs, pshard):
    if pshard is None or slot_specs is None:
        return slot_ps
    is_axes = lambda t: t is None or (isinstance(t, tuple) and all(
        isinstance(a, (str, type(None))) for a in t))
    out = []
    for ps, sp in zip(slot_ps, slot_specs):
        leaves, treedef = jax.tree.flatten(ps)
        specs = treedef.flatten_up_to(sp)
        out.append(jax.tree.unflatten(
            treedef, [pshard(l, a) if isinstance(a, tuple) else l
                      for l, a in zip(leaves, specs)]))
    return out


def _run_segments_seq(params, cfg: ModelConfig, x, *, positions, cond, mesh,
                      states=None, pasts=None, k_positions=None,
                      tok_valid=None, shard=_IDENT, collect_cache=False,
                      param_specs=None, pshard=None):
    """Run all segments in sequence mode.  states (optional) mirror the
    segment/slot structure with [R, ...] stacked leaves (recurrent only);
    pasts (optional, chunked prefill) likewise mirror it with previous
    chunks' attention cache entries stacked [R, B, P, ...], attended via
    ``k_positions``.  Returns (x, caches, aux_total)."""
    aux_total = jnp.float32(0.0)
    caches: List[List[Any]] = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        kinds = [parse_kind(s) for s in pattern]
        slot_params = params["segments"][si]
        seg_states = states["segments"][si] if states is not None else None
        seg_pasts = pasts["segments"][si] if pasts is not None else None

        slot_specs = (_strip_layers(param_specs["segments"][si])
                      if param_specs is not None else None)

        if cfg.unroll_layers:
            entries_all = []

            def one_repeat(xx, aux, slot_ps, slot_sts, slot_pst):
                slot_ps = _constrain_slots(slot_ps, slot_specs, pshard)
                entries = []
                for j, kind in enumerate(kinds):
                    st = slot_sts[j] if slot_sts is not None else None
                    pst = slot_pst[j] if slot_pst is not None else None
                    xx, entry, a = _apply_block_seq(
                        slot_ps[j], cfg, kind, xx, positions=positions,
                        cond=cond, mesh=mesh, state=st, past=pst,
                        k_positions=k_positions, tok_valid=tok_valid,
                        shard=shard)
                    entries.append(entry)
                    aux = aux + a
                return xx, aux, entries

            fn = (jax.checkpoint(one_repeat, static_argnums=())
                  if cfg.remat else one_repeat)
            for r in range(repeats):
                slot_ps_r = jax.tree.map(lambda a: a[r], slot_params)
                sts_r = (jax.tree.map(lambda a: a[r], seg_states)
                         if seg_states is not None else None)
                pst_r = (jax.tree.map(lambda a: a[r], seg_pasts)
                         if seg_pasts is not None else None)
                x, aux_total, entries = fn(x, aux_total, slot_ps_r, sts_r,
                                           pst_r)
                entries_all.append(entries)
            if collect_cache:
                stacked = []
                for j in range(len(kinds)):
                    stacked.append(jax.tree.map(
                        lambda *xs: jnp.stack(xs, axis=0),
                        *[e[j] for e in entries_all]))
                caches.append(stacked)
            else:
                caches.append(None)
            continue

        def body(carry, per_repeat):
            xx, aux = carry
            slot_ps, slot_sts, slot_pst = per_repeat
            slot_ps = _constrain_slots(slot_ps, slot_specs, pshard)
            entries = []
            for j, kind in enumerate(kinds):
                st = slot_sts[j] if slot_sts is not None else None
                pst = slot_pst[j] if slot_pst is not None else None
                xx, entry, a = _apply_block_seq(
                    slot_ps[j], cfg, kind, xx, positions=positions, cond=cond,
                    mesh=mesh, state=st, past=pst, k_positions=k_positions,
                    tok_valid=tok_valid, shard=shard)
                entries.append(entry)
            return (xx, aux + a), entries

        body_fn = jax.checkpoint(body) if cfg.remat else body
        has_st, has_pst = seg_states is not None, seg_pasts is not None
        dummy = [jnp.zeros((repeats,))] * len(kinds)

        def body_fn2(carry, pr, _st=has_st, _pst=has_pst):
            slot_ps, sts, pst = pr
            return body_fn(carry, (slot_ps, sts if _st else None,
                                   pst if _pst else None))

        xs = (slot_params,
              seg_states if has_st else dummy,
              seg_pasts if has_pst else dummy)
        (x, aux_total), entries = jax.lax.scan(body_fn2, (x, aux_total), xs)
        caches.append(entries if collect_cache else None)
    return x, caches, aux_total


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None, cond=None,
            mesh=None, shard=_IDENT, param_specs=None, pshard=None):
    """Training forward.  tokens: [B,S_text]; extra_embeds (VLM/audio
    frontend stub): [B,P,d] prepended before the token embeddings.
    Returns (logits [B,S,V], aux_loss)."""
    x = L.embed(params["embed"], cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)[None]
    x = shard(x, ("batch", "seq", "embed"))
    if cond is not None:
        cond = cond.astype(x.dtype)
    x, _, aux = _run_segments_seq(params, cfg, x, positions=positions,
                                  cond=cond, mesh=mesh, shard=shard,
                                  param_specs=param_specs, pshard=pshard)
    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed(params["embed"], cfg, x)
    logits = shard(logits, ("batch", "seq", "vocab"))
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    """Decode cache pytree mirroring the segment structure."""
    segs = []
    for pattern, repeats in cfg.segments:
        slots = []
        for kind_s in pattern:
            kind = parse_kind(kind_s)
            if kind.is_attention:
                t = (min(cfg.window_size, max_len)
                     if kind.base == "local" else max_len)
                if kind.mla:
                    m = cfg.mla
                    c = {"ckv": jnp.zeros((repeats, batch, t, m.kv_lora_rank),
                                          dtype),
                         "krope": jnp.zeros((repeats, batch, t, m.qk_rope_dim),
                                            dtype),
                         "pos": jnp.full((repeats, batch, t), -1, jnp.int32)}
                else:
                    kv, hd = cfg.num_kv_heads, cfg.head_dim
                    c = {"k": jnp.zeros((repeats, batch, t, kv, hd), dtype),
                         "v": jnp.zeros((repeats, batch, t, kv, hd), dtype),
                         "pos": jnp.full((repeats, batch, t), -1, jnp.int32)}
            else:
                zero = {"mlstm": R.mlstm_zero_state, "slstm": R.slstm_zero_state,
                        "rglru": R.rglru_zero_state}[kind.base]
                c = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (repeats,) + a.shape),
                    zero(cfg, batch))
            slots.append(c)
        segs.append(slots)
    return {"segments": segs}


def cache_specs(cfg: ModelConfig, shape_kind: str = "decode"):
    """Logical-axis spec tree matching ``init_cache`` output."""
    segs = []
    for pattern, repeats in cfg.segments:
        slots = []
        for kind_s in pattern:
            kind = parse_kind(kind_s)
            if kind.is_attention:
                if kind.mla:
                    c = {"ckv": ("layers", "batch", "kv_seq", None),
                         "krope": ("layers", "batch", "kv_seq", None),
                         "pos": ("layers", "batch", "kv_seq")}
                else:
                    c = {"k": ("layers", "batch", "kv_seq", None, None),
                         "v": ("layers", "batch", "kv_seq", None, None),
                         "pos": ("layers", "batch", "kv_seq")}
            else:
                zero = {"mlstm": R.mlstm_zero_state, "slstm": R.slstm_zero_state,
                        "rglru": R.rglru_zero_state}[kind.base]
                proto = zero(cfg, 1)
                c = jax.tree.map(
                    lambda a: ("layers", "batch") + (None,) * (a.ndim - 1),
                    proto)
            slots.append(c)
        segs.append(slots)
    return {"segments": segs}


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_pos, *,
                cond=None, mesh=None, shard=_IDENT):
    """One decode step.  tokens: [B,1]; cur_pos: [B] int32 (current length).
    Returns (logits [B,1,V], new_cache)."""
    x = L.embed(params["embed"], cfg, tokens)
    x = shard(x, ("batch", "seq", "embed"))
    if cond is not None:
        cond = cond.astype(x.dtype)
    new_segs = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        kinds = [parse_kind(s) for s in pattern]
        slot_params = params["segments"][si]
        slot_caches = cache["segments"][si]

        def body(xx, per_repeat):
            slot_ps, slot_cs = per_repeat
            new_cs = []
            for j, kind in enumerate(kinds):
                xx, nc = _apply_block_decode(
                    slot_ps[j], cfg, kind, xx, slot_cs[j], cur_pos=cur_pos,
                    cond=cond, mesh=mesh, shard=shard)
                new_cs.append(nc)
            return xx, new_cs

        if cfg.unroll_layers:
            reps = []
            for r in range(repeats):
                per = jax.tree.map(lambda a: a[r], (slot_params, slot_caches))
                x, ncs = body(x, per)
                reps.append(ncs)
            new_slot_caches = [
                jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                             *[rep[j] for rep in reps])
                for j in range(len(kinds))]
        else:
            x, new_slot_caches = jax.lax.scan(body, x,
                                              (slot_params, slot_caches))
        new_segs.append(new_slot_caches)
    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed(params["embed"], cfg, x)
    return logits, {"segments": new_segs}


def prefill(params, cfg: ModelConfig, tokens, *, extra_embeds=None, cond=None,
            mesh=None, shard=_IDENT, param_specs=None, pshard=None):
    """Prefill: forward pass that also returns a populated cache.
    Returns (last_logits [B,1,V], cache)."""
    x = L.embed(params["embed"], cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)[None]
    x = shard(x, ("batch", "seq", "embed"))
    if cond is not None:
        cond = cond.astype(x.dtype)
    x, caches, _ = _run_segments_seq(params, cfg, x, positions=positions,
                                     cond=cond, mesh=mesh, shard=shard,
                                     collect_cache=True,
                                     param_specs=param_specs, pshard=pshard)
    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed(params["embed"], cfg, x[:, -1:])

    # Assemble the cache pytree: attention entries -> (k, v, pos); recurrent
    # entries are already final states stacked [R, ...] by the scan.
    segs = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        kinds = [parse_kind(p_) for p_ in pattern]
        slots = []
        for j, kind in enumerate(kinds):
            e = caches[si][j]
            if kind.is_attention:
                window = cfg.window_size if kind.base == "local" else 0
                pos = jnp.broadcast_to(jnp.arange(s)[None, None],
                                       (repeats, b, s)).astype(jnp.int32)
                if window > 0 and s > window:
                    e = jax.tree.map(lambda a: a[:, :, -window:], e)
                    pos = pos[:, :, -window:]
                    # Ring alignment: decode overwrites slot cur_pos %
                    # window, so slot j must hold the position == j (mod
                    # window).  The chronological clip above puts position
                    # s-window+j at slot j; roll by s % window to restore
                    # the ring invariant -- without it, a prompt with
                    # s % window >= 2 had its next decode step overwrite a
                    # position still inside the attention window.
                    shift = s % window
                    if shift:
                        e = jax.tree.map(
                            lambda a: jnp.roll(a, shift, axis=2), e)
                        pos = jnp.roll(pos, shift, axis=2)
                if kind.mla:
                    slots.append({"ckv": e["ckv"], "krope": e["krope"],
                                  "pos": pos})
                else:
                    slots.append({"k": e["k"], "v": e["v"], "pos": pos})
            else:
                slots.append(e)
        segs.append(slots)
    return logits, {"segments": segs}


def pad_cache(cache, cfg: ModelConfig, max_len: int):
    """Pad prefill-produced attention caches out to `max_len` capacity
    (pos entries -1 == empty).  Recurrent states pass through."""
    segs = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        slots = []
        for j, kind_s in enumerate(pattern):
            kind = parse_kind(kind_s)
            c = cache["segments"][si][j]
            if kind.is_attention:
                window = cfg.window_size if kind.base == "local" else 0
                cap = min(window, max_len) if window > 0 else max_len
                cur = c["pos"].shape[2]
                if cur < cap:
                    pad = cap - cur

                    def padk(a, fill=0):
                        w = [(0, 0)] * a.ndim
                        w[2] = (0, pad)
                        return jnp.pad(a, w, constant_values=fill)

                    c = {k_: (padk(v, -1) if k_ == "pos" else padk(v))
                         for k_, v in c.items()}
            slots.append(c)
        segs.append(slots)
    return {"segments": segs}


# ---------------------------------------------------------------------------
# fully-paged serving path: batched prefill + decode over shared page pools
# ---------------------------------------------------------------------------


def state_slot_meta(cfg: ModelConfig):
    """EVERY state-bearing slot in execution order: (si, j, repeats,
    window, kind) -- plain/local attention, MLA and recurrent cells alike.

    This is the layer enumeration the shared page pools mirror: one set of
    geometry leaves per (segment, slot), stacked ``[repeats, ...]``
    exactly like the parameter tree, so the paged decode scan can slice
    pools and params with the same index."""
    out = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        for j, kind_s in enumerate(pattern):
            kind = parse_kind(kind_s)
            window = (cfg.window_size
                      if kind.is_attention and kind.base == "local" else 0)
            out.append((si, j, repeats, window, kind))
    return out


def attn_slot_meta(cfg: ModelConfig):
    """The attention subset of ``state_slot_meta`` (same tuple layout)."""
    return [m for m in state_slot_meta(cfg) if m[4].is_attention]


def attn_slot_index(cfg: ModelConfig, si: int, j: int) -> int:
    """Index of segment ``si`` slot ``j`` in the ``state_slot_meta`` order
    (== its leaf index in the shared pools' layered storage).  The slot
    must be an attention slot (its leaves are k/v or ckv/krope)."""
    for i, (si_, j_, _, _, kind) in enumerate(state_slot_meta(cfg)):
        if (si_, j_) == (si, j):
            if not kind.is_attention:
                break
            return i
    raise ValueError(f"({si}, {j}) is not an attention slot of {cfg.name}")


def _zero_state(cfg: ModelConfig, kind: LayerKind, batch: int):
    zero = {"mlstm": R.mlstm_zero_state, "slstm": R.slstm_zero_state,
            "rglru": R.rglru_zero_state}[kind.base]
    return zero(cfg, batch)


def state_dim(cfg: ModelConfig, kind: LayerKind) -> int:
    """Flattened per-row float count of one recurrent cell's state -- the
    trailing dim of its pool leaf (one logical "page" per request)."""
    proto = _zero_state(cfg, kind, 1)
    return sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(proto))


def pack_state(state) -> jnp.ndarray:
    """Flatten a recurrent state pytree to f32[B, state_dim] (canonical
    tree-leaf order).  Pure reshape/concat -- bit-exact round trip."""
    leaves = jax.tree.leaves(state)
    b = leaves[0].shape[0]
    return jnp.concatenate(
        [a.reshape(b, -1).astype(jnp.float32) for a in leaves], axis=1)


def unpack_state(flat: jnp.ndarray, proto):
    """Inverse of ``pack_state`` against a same-structure prototype (e.g.
    the cell's zero state at the right batch)."""
    leaves, treedef = jax.tree.flatten(proto)
    b = flat.shape[0]
    out, o = [], 0
    for a in leaves:
        n = int(np.prod(a.shape[1:]))
        out.append(flat[:, o:o + n].reshape((b,) + a.shape[1:])
                   .astype(a.dtype))
        o += n
    return jax.tree.unflatten(treedef, out)


def slot_leaf_specs(cfg: ModelConfig, page_size: int):
    """Per-geometry leaf specs for ``SharedPagedPools.attach_layered``:
    one ``(repeats, {leaf_name: trailing_shape})`` entry per
    ``state_slot_meta`` slot.  Plain attention pages hold (k, v) token
    rows; MLA pages hold compressed (ckv, krope) rows shared across
    heads; recurrent cells hold one fixed-size state vector per request
    (a single logical page, tiered like any other)."""
    specs = []
    for (_, _, repeats, _, kind) in state_slot_meta(cfg):
        if kind.is_attention and kind.mla:
            m = cfg.mla
            leaves = {"ckv": (page_size, m.kv_lora_rank),
                      "krope": (page_size, m.qk_rope_dim)}
        elif kind.is_attention:
            leaves = {"k": (page_size, cfg.num_kv_heads, cfg.head_dim),
                      "v": (page_size, cfg.num_kv_heads, cfg.head_dim)}
        else:
            leaves = {"state": (state_dim(cfg, kind),)}
        specs.append((repeats, leaves))
    return specs


def has_state_pages(cfg: ModelConfig) -> bool:
    """Whether any slot is a recurrent cell (the request then carries one
    extra logical "state page" after its KV pages)."""
    return any(not k.is_attention for (_, _, _, _, k) in state_slot_meta(cfg))


def has_attention(cfg: ModelConfig) -> bool:
    return any(k.is_attention for (_, _, _, _, k) in state_slot_meta(cfg))


def paged_supported(cfg: ModelConfig) -> bool:
    """Every registered geometry now runs fully paged: plain/local
    attention (k, v) pages, MLA compressed (ckv, krope) pages, recurrent
    state slots, shared read-only prefix pages and cross-attention
    conditioning are all expressible on the shared slot pool
    (``slot_leaf_specs``).  Kept as an API point for callers that gate on
    it; always True for the config registry."""
    return True


def batched_prefill_supported(cfg: ModelConfig) -> bool:
    """Right-padded batched prefill is exact iff no layer carries
    sequential state across positions (recurrent cells would consume the
    padding tokens of short rows).  Attention rows are causal, so a row's
    valid prefix never sees the padding."""
    for pattern, _ in cfg.segments:
        for kind_s in pattern:
            if not parse_kind(kind_s).is_attention:
                return False
    return True


def prefill_batched(params, cfg: ModelConfig, tokens, lengths, *, cond=None,
                    extra_embeds=None, mesh=None, shard=_IDENT):
    """Batched-admission prefill: one packed forward over right-padded
    prompts.  tokens: [B, Smax] int32 (rows padded with any id); lengths:
    int32[B] true row length *including* any prepended prefix.

    ``extra_embeds`` ([B, P, d], the shared VLM/audio prefix) is
    prepended before the token embeddings exactly as in ``prefill``; the
    cache timeline then starts at the prefix, so page writers slice it by
    absolute position.

    Returns (last_logits [B,1,V], cache) where ``last_logits[b]`` is the
    logits at position ``lengths[b] - 1`` and the cache keeps the FULL
    padded timeline (no window clipping -- per-request extraction happens
    in ``row_cache_from_batched`` / the paged page-writer, which know each
    row's true length).  ``pos`` is per-row masked: slot t holds t for
    t < lengths[b], else -1.  Causality makes each row's valid prefix
    independent of its padding, so row b's logits and cache match a
    per-request prefill of its own prompt.
    """
    if not batched_prefill_supported(cfg):
        raise ValueError(f"{cfg.name}: batched prefill needs all-attention "
                         "layers (recurrent state would fold in padding)")
    x = L.embed(params["embed"], cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)[None]
    x = shard(x, ("batch", "seq", "embed"))
    if cond is not None:
        cond = cond.astype(x.dtype)
    in_row = jnp.arange(s)[None] < jnp.asarray(lengths)[:, None]
    x, caches, _ = _run_segments_seq(params, cfg, x, positions=positions,
                                     cond=cond, mesh=mesh, shard=shard,
                                     tok_valid=in_row, collect_cache=True)
    x = L.rms_norm(x, params["final_norm"])
    last = x[jnp.arange(b), jnp.asarray(lengths) - 1][:, None]
    logits = L.unembed(params["embed"], cfg, last)

    pos_row = jnp.where(in_row, jnp.arange(s)[None], -1).astype(jnp.int32)
    segs = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        slots = []
        for j, kind_s in enumerate(pattern):
            kind = parse_kind(kind_s)
            e = caches[si][j]
            pos = jnp.broadcast_to(pos_row[None], (repeats, b, s))
            if kind.mla:
                slots.append({"ckv": e["ckv"], "krope": e["krope"],
                              "pos": pos})
            else:
                slots.append({"k": e["k"], "v": e["v"], "pos": pos})
        segs.append(slots)
    return logits, _with_moe_counts({"segments": segs}, caches)


def _with_moe_counts(cache, caches):
    """``cache`` plus, where any layer has experts, ``moe`` int32[2]: the
    pass's token-expert pairs routed to held experts and held experts
    touched, summed over layers (``moe_apply_grouped``'s counts)."""
    counts = [e["moe"].sum(axis=0) for seg in caches for e in seg
              if isinstance(e, dict) and "moe" in e]
    if counts:
        cache["moe"] = sum(counts)
    return cache


def prefill_chunk(params, cfg: ModelConfig, tokens, lengths, past=None, *,
                  start: int = 0, cond=None, mesh=None, shard=_IDENT):
    """One width-bounded chunk of a batched-admission prefill.

    Splits ``prefill_batched``'s packed forward into chunks over absolute
    positions so long-prompt admission can interleave with macro launches
    (docs/serving.md, "Pipelined macro loop").  ``tokens``: [B, C], the
    slice of the right-padded prompt batch covering absolute positions
    ``[start, start+C)``; ``lengths``: int32[B] full true row lengths;
    ``past``: the accumulated cache of every previous chunk (leaves
    stacked [R, B, start, ...]; build it with ``chunk_past_extend`` from
    this function's own returns).  ``start`` is static per jit
    specialisation -- it fixes the past's time extent.

    The past is kept at its exact length (no padding): each chunk's keys
    are ``past ++ own`` at the same key indices the packed pass uses, so
    every valid lane reduces over the identical value set.  Reduction
    *widths* still differ from the packed pass (t grows chunk by chunk),
    so logits agree to reduction-order ULP noise -- the same tolerance
    class as dense-vs-paged attention, and token-identical through the
    sampler (the chunked-prefill parity test pins this).

    Returns (logits [B,1,V], cache_chunk): ``logits[b]`` is taken at the
    row's final position clamped into this chunk, meaningful only when
    ``start <= lengths[b]-1 < start+C`` (the caller keeps that chunk's
    row); ``cache_chunk`` matches the corresponding position range of a
    ``prefill_batched`` cache (``pos`` masked per row, -1 beyond its
    length).  No ``extra_embeds``: admissions with a VLM/audio prefix
    keep the packed path.
    """
    if not batched_prefill_supported(cfg):
        raise ValueError(f"{cfg.name}: chunked prefill needs all-attention "
                         "layers (recurrent state would fold in padding)")
    x = L.embed(params["embed"], cfg, tokens)
    b, c = x.shape[0], x.shape[1]
    start = int(start)
    positions = start + jnp.arange(c)[None]
    k_positions = jnp.arange(start + c)[None]
    x = shard(x, ("batch", "seq", "embed"))
    if cond is not None:
        cond = cond.astype(x.dtype)
    x, caches, _ = _run_segments_seq(params, cfg, x, positions=positions,
                                     cond=cond, mesh=mesh, shard=shard,
                                     pasts=past, k_positions=k_positions,
                                     tok_valid=positions < jnp.asarray(
                                         lengths)[:, None],
                                     collect_cache=True)
    x = L.rms_norm(x, params["final_norm"])
    take = jnp.clip(jnp.asarray(lengths) - 1 - start, 0, c - 1)
    last = x[jnp.arange(b), take][:, None]
    logits = L.unembed(params["embed"], cfg, last)

    pos_row = jnp.where(positions < jnp.asarray(lengths)[:, None],
                        positions, -1).astype(jnp.int32)
    segs = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        slots = []
        for j, kind_s in enumerate(pattern):
            kind = parse_kind(kind_s)
            e = caches[si][j]
            pos = jnp.broadcast_to(pos_row[None], (repeats, b, c))
            if kind.mla:
                slots.append({"ckv": e["ckv"], "krope": e["krope"],
                              "pos": pos})
            else:
                slots.append({"k": e["k"], "v": e["v"], "pos": pos})
        segs.append(slots)
    return logits, {"segments": segs}


def chunk_past_extend(past, cache_chunk):
    """Accumulate chunked-prefill past: append ``cache_chunk`` (a
    ``prefill_chunk`` second return) onto ``past`` along the time axis,
    dropping the per-row ``pos`` (the next chunk rebuilds key positions
    as the contiguous ``arange(start+C)``).  ``past=None`` starts the
    accumulation.  Eager concatenation on (possibly lazy) device arrays:
    it dispatches without blocking, so the scheduler can extend the past
    behind an in-flight macro scan."""
    segs = []
    for si, slots in enumerate(cache_chunk["segments"]):
        new_slots = []
        for j, e in enumerate(slots):
            ent = {k_: v_ for k_, v_ in e.items() if k_ != "pos"}
            if past is not None:
                old = past["segments"][si][j]
                ent = {k_: jnp.concatenate([old[k_], v_], axis=2)
                       for k_, v_ in ent.items()}
            new_slots.append(ent)
        segs.append(new_slots)
    return {"segments": segs}


def row_cache_from_batched(cache, cfg: ModelConfig, bi: int, length: int,
                           max_len: int):
    """Extract request ``bi`` from a ``prefill_batched`` cache as the row
    pytree a packed dense cache expects at one batch row: attention
    entries [R, cap, ...] with ring-consistent window layout (slot ==
    pos % window) and pos == -1 beyond ``length`` -- exactly what
    per-request ``prefill`` + ``pad_cache`` would have produced, modulo
    values at masked slots (which attention zeroes out)."""
    segs = []
    for si, (pattern, repeats) in enumerate(cfg.segments):
        slots = []
        for j, kind_s in enumerate(pattern):
            kind = parse_kind(kind_s)
            e = cache["segments"][si][j]
            window = cfg.window_size if kind.base == "local" else 0
            cap = min(window, max_len) if window > 0 else max_len
            s = e["pos"].shape[2]
            if length > cap:
                # window ring: slot i holds the unique in-window position
                # with pos % cap == i (the invariant decode's ring
                # overwrite preserves)
                lo = length - cap
                idx = lo + (np.arange(cap) - lo) % cap
                pos_np = idx
            else:
                idx = np.minimum(np.arange(cap), s - 1)
                pos_np = np.where(np.arange(cap) < length,
                                  np.arange(cap), -1)
            src = jnp.asarray(idx, jnp.int32)
            pos_row = jnp.broadcast_to(
                jnp.asarray(pos_np, jnp.int32)[None], (repeats, cap))
            row = {key_: (pos_row if key_ == "pos" else a[:, bi, src])
                   for key_, a in e.items()}
            slots.append(row)
        segs.append(slots)
    return {"segments": segs}


def decode_step_paged(params, cfg: ModelConfig, kv, tables, gid_tables,
                      tokens, cur_pos, *, page_size: int,
                      impl: Optional[str] = None, cond=None, state_cols=None,
                      mesh=None, shard=_IDENT):
    """One decode step with EVERY state-bearing layer reading and writing
    the shared paged pools -- the fully-paged serving hot path (no dense
    per-row cache exists).  Plain attention gathers (k, v) pages through
    ``kernels.paged_attention``; MLA gathers compressed (ckv, krope)
    pages through ``kernels.paged_attention_mla``; recurrent cells read
    and write one packed state page per request.

    kv: the layered pool pytree (``SharedPagedPools.kv_view``): one leaf
        set per ``state_slot_meta`` entry, named per geometry
        (``k/v``, ``ckv/krope``, ``state``; absent leaves are None).  HBM
        leaves are the resident slot pools the kernels gather from, host
        leaves the write-through backing copy that survives eviction.
    tables:     int32[B, n_row_pages] physical HBM slot per row page
                (-1 = padding / inactive row; reads are masked by length,
                writes are dropped).
    gid_tables: int32[B, n_row_pages] global logical page id per row page
                (-1 = padding), for the host-copy write-through.
    tokens: [B,1]; cur_pos: int32[B], position of the token being decoded
                (-1 = inactive row).
    cond:       [B, T, d] cross-attention conditioning for xattn slots.
    state_cols: int32[B] column of each request's state page in its row
                tables (-1 = none); required iff the config has recurrent
                slots.

    Returns (logits [B,1,V], new_kv, page_mass f32[B, n_row_pages]) where
    ``page_mass`` is the per-request access mass per row page aggregated
    over ALL state-bearing layers (head-normalised attention mass per
    attention layer, a unit touch on the state page per recurrent layer,
    mean across layers -- each active row sums to ~1): the true aggregate
    traffic signal online Cori tunes from.
    """
    return _paged_decode_core(params, cfg, kv, tables, gid_tables, tokens,
                              cur_pos, page_size=page_size, impl=impl,
                              cond=cond, state_cols=state_cols, mesh=mesh,
                              shard=shard)[:3]


def _paged_decode_core(params, cfg: ModelConfig, kv, tables, gid_tables,
                       tokens, cur_pos, *, page_size: int,
                       impl: Optional[str], cond=None, state_cols=None,
                       mesh=None, shard=_IDENT):
    """The traced body shared by ``decode_step_paged`` (one launch per
    token) and ``decode_macro_step`` (one launch per movement period).
    ``impl=None`` takes the paged kernels' path from the platform: the
    compiled Pallas kernels on a TPU, the jnp reference on the CPU.
    Returns ``decode_step_paged``'s three results and the expert layers'
    counts, int32[2] (``moe_apply_grouped``; active rows only)."""
    impl = impl or ops.default_impl("reference")
    b = tokens.shape[0]
    n_row_pages = tables.shape[1]
    active = cur_pos >= 0
    lengths = jnp.where(active, cur_pos + 1, 0)
    safe_pos = jnp.maximum(cur_pos, 0)
    pg = safe_pos // page_size
    off = safe_pos % page_size
    wslot = tables[jnp.arange(b), pg]          # -1 when padding/inactive
    wgid = gid_tables[jnp.arange(b), pg]
    big = jnp.int32(2 ** 30)                   # out of bounds => dropped
    wslot = jnp.where(active & (wslot >= 0), wslot, big)
    wgid = jnp.where(active & (wgid >= 0), wgid, big)
    if state_cols is None and has_state_pages(cfg):
        raise ValueError(f"{cfg.name}: paged decode over recurrent slots "
                         "needs state_cols (column of each row's state "
                         "page in `tables`)")
    if state_cols is not None:
        scol = jnp.maximum(jnp.asarray(state_cols, jnp.int32), 0)
        sslot = tables[jnp.arange(b), scol]
        sgid = gid_tables[jnp.arange(b), scol]
        svalid = active & (jnp.asarray(state_cols) >= 0) & (sslot >= 0)
        srd = jnp.maximum(sslot, 0)            # clamped read index
        swslot = jnp.where(svalid, sslot, big)
        swgid = jnp.where(svalid, sgid, big)
        # a recurrent layer touches its state page once per step: a unit
        # of access mass at the state column, same scale as an attention
        # layer's head-normalised row (sums to ~1)
        smass = jnp.where(
            svalid[:, None] & (jnp.arange(n_row_pages)[None]
                               == scol[:, None]), 1.0, 0.0)

    x = L.embed(params["embed"], cfg, tokens)
    x = shard(x, ("batch", "seq", "embed"))
    if cond is not None:
        cond = cond.astype(x.dtype)
    mass_sum = jnp.zeros((b, n_row_pages), jnp.float32)
    moe_sum = jnp.zeros((2,), jnp.int32)
    n_layers = 0
    new_kv = {k_: list(v_) for k_, v_ in kv.items()}

    def _block_tail(xx, slot_p, kind):
        """Post-core residual stack shared by every geometry: cross-attn
        conditioning, MoE / MLP.  Returns (xx, expert counts int32[2])."""
        counts = jnp.zeros((2,), jnp.int32)
        if kind.xattn and cond is not None:
            hx = L.rms_norm(xx, slot_p["norm_x"])
            cpos = jnp.arange(cond.shape[1])[None]
            cmask = jnp.ones((1, 1, cond.shape[1]), bool)
            o2, _ = L.attention_apply(slot_p["xattn"], cfg, hx, cond,
                                      cur_pos[:, None], cmask,
                                      kv_positions=cpos, use_rope=False)
            xx = xx + o2
        if kind.moe:
            h2 = L.rms_norm(xx, slot_p["norm2"])
            o2, _, counts = M.moe_apply(slot_p["moe"], cfg, h2, mesh,
                                        valid=active[:, None])
            xx = xx + o2
        elif cfg.d_ff > 0 and "mlp" in slot_p:
            h2 = L.rms_norm(xx, slot_p["norm2"])
            xx = xx + L.mlp_apply(slot_p["mlp"], cfg, h2)
        return shard(xx, ("batch", "seq", "embed")), counts

    def attn_block(xx, slot_p, leaves, kind):
        """Plain/local attention against its (k, v) pool leaves
        (per-repeat slices: [hbm_pages|n_logical, page, KV, D])."""
        kh, vh, khost, vhost = leaves
        window = cfg.window_size if kind.base == "local" else 0
        h = L.rms_norm(xx, slot_p["norm1"])
        q = jnp.einsum("bsd,dhk->bshk", h,
                       slot_p["attn"]["wq"].astype(h.dtype))
        k_new = jnp.einsum("bsd,dhk->bshk", h,
                           slot_p["attn"]["wk"].astype(h.dtype))
        v_new = jnp.einsum("bsd,dhk->bshk", h,
                           slot_p["attn"]["wv"].astype(h.dtype))
        if cfg.qk_norm:
            q = L.rms_norm(q, slot_p["attn"]["q_norm"])
            k_new = L.rms_norm(k_new, slot_p["attn"]["k_norm"])
        q = L.rope(q, cur_pos[:, None], cfg.rope_theta)
        k_new = L.rope(k_new, cur_pos[:, None], cfg.rope_theta)
        # write-through: the decoding token's KV lands in its HBM slot
        # page AND the host backing page before the gather, so the kernel
        # attends the current token too
        k1 = k_new[:, 0].astype(kh.dtype)
        v1 = v_new[:, 0].astype(vh.dtype)
        kh = kh.at[wslot, off].set(k1, mode="drop")
        vh = vh.at[wslot, off].set(v1, mode="drop")
        khost = khost.at[wgid, off].set(k1, mode="drop")
        vhost = vhost.at[wgid, off].set(v1, mode="drop")
        ctx, mass = ops.paged_attention(
            q[:, 0], kh, vh, tables, lengths, window=window,
            softcap=cfg.softcap, return_mass=True, impl=impl)
        out = jnp.einsum("bshk,hkd->bsd", ctx[:, None],
                         slot_p["attn"]["wo"].astype(xx.dtype))
        xx, counts = _block_tail(xx + out, slot_p, kind)
        return xx, (kh, vh, khost, vhost, mass, counts)

    def mla_block(xx, slot_p, leaves, kind):
        """Absorbed-matrix MLA against its compressed (ckv, krope) pool
        leaves (per-repeat slices: [hbm_pages|n_logical, page, R|K]) --
        the paged analogue of ``layers.mla_decode``."""
        ckvh, krh, ckvhost, krhost = leaves
        p = slot_p["attn"]
        h = L.rms_norm(xx, slot_p["norm1"])
        q_nope, q_rope, c_new, kr_new = L.mla_project(p, cfg, h,
                                                      cur_pos[:, None])
        c1 = c_new[:, 0].astype(ckvh.dtype)
        r1 = kr_new[:, 0].astype(krh.dtype)
        ckvh = ckvh.at[wslot, off].set(c1, mode="drop")
        krh = krh.at[wslot, off].set(r1, mode="drop")
        ckvhost = ckvhost.at[wgid, off].set(c1, mode="drop")
        krhost = krhost.at[wgid, off].set(r1, mode="drop")
        q_abs = L.mm(q_nope, p["w_uk"], "bshk,rhk->bshr")
        ctx, mass = ops.paged_attention_mla(
            q_abs[:, 0], q_rope[:, 0].astype(q_abs.dtype), ckvh, krh, tables,
            lengths, scale=cfg.mla_scale, return_mass=True, impl=impl)
        out = L.mm(ctx[:, None], p["w_uv"], "bshr,rhk->bshk")
        out = L.mm(out, p["wo"], "bshk,hkd->bsd")
        xx, counts = _block_tail(xx + out, slot_p, kind)
        return xx, (ckvh, krh, ckvhost, krhost, mass, counts)

    def state_block(xx, slot_p, leaves, kind):
        """Recurrent cell against its packed state page (per-repeat
        slices: [hbm_pages|n_logical, state_dim]).  The cell state lives
        in the pool like any page: read from the HBM slot, step, write
        back through both tiers."""
        sth, sthost = leaves
        h = L.rms_norm(xx, slot_p["norm1"])
        proto = _zero_state(cfg, kind, b)
        state = unpack_state(sth[srd], proto)
        step = {"mlstm": R.mlstm_step, "slstm": R.slstm_step,
                "rglru": R.rglru_step}[kind.base]
        out, new_state = step(slot_p["cell"], cfg, h, state)
        flat = pack_state(new_state).astype(sth.dtype)
        sth = sth.at[swslot].set(flat, mode="drop")
        sthost = sthost.at[swgid].set(flat, mode="drop")
        xx, counts = _block_tail(xx + out, slot_p, kind)
        return xx, (sth, sthost, smass, counts)

    def slot_leaves(kind, li):
        if kind.is_attention and kind.mla:
            return (kv["ckv_hbm"][li], kv["krope_hbm"][li],
                    kv["ckv_host"][li], kv["krope_host"][li])
        if kind.is_attention:
            return (kv["k_hbm"][li], kv["v_hbm"][li],
                    kv["k_host"][li], kv["v_host"][li])
        return (kv["state_hbm"][li], kv["state_host"][li])

    def store_leaves(kind, li, upd):
        if kind.is_attention and kind.mla:
            (new_kv["ckv_hbm"][li], new_kv["krope_hbm"][li],
             new_kv["ckv_host"][li], new_kv["krope_host"][li]) = upd[:-2]
        elif kind.is_attention:
            (new_kv["k_hbm"][li], new_kv["v_hbm"][li],
             new_kv["k_host"][li], new_kv["v_host"][li]) = upd[:-2]
        else:
            new_kv["state_hbm"][li], new_kv["state_host"][li] = upd[:-2]
        return upd[-2:]

    def one_block(xx, slot_p, leaves, kind):
        if kind.is_attention and kind.mla:
            return mla_block(xx, slot_p, leaves, kind)
        if kind.is_attention:
            return attn_block(xx, slot_p, leaves, kind)
        return state_block(xx, slot_p, leaves, kind)

    li = 0
    for si, (pattern, repeats) in enumerate(cfg.segments):
        kinds = [parse_kind(s_) for s_ in pattern]
        slot_params = params["segments"][si]
        nslots = len(kinds)
        seg_leaves = [slot_leaves(kinds[j], li + j) for j in range(nslots)]

        # execution order matches decode_step: the whole pattern runs per
        # repeat (slots inner, repeats outer)
        def body(xx, per_repeat):
            slot_ps, slot_lvs = per_repeat
            new_lvs = []
            for j, kind in enumerate(kinds):
                xx, upd = one_block(xx, slot_ps[j], slot_lvs[j], kind)
                new_lvs.append(upd)
            return xx, new_lvs

        if cfg.unroll_layers or repeats == 1:
            reps = []
            for r in range(repeats):
                per = jax.tree.map(lambda a: a[r], (slot_params, seg_leaves))
                x, lvs = body(x, per)
                reps.append(lvs)
            stacked = [jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                                    *[rep[j] for rep in reps])
                       for j in range(nslots)]
        else:
            x, stacked = jax.lax.scan(body, x, (slot_params, seg_leaves))
        for j in range(nslots):
            mass, counts = store_leaves(kinds[j], li + j, stacked[j])
            mass_sum = mass_sum + mass.sum(axis=0)
            moe_sum = moe_sum + counts.sum(axis=0)
            n_layers += repeats
        li += nslots

    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed(params["embed"], cfg, x)
    page_mass = mass_sum / max(1, n_layers)
    page_mass = jnp.where(active[:, None], page_mass, 0.0)
    return logits, new_kv, page_mass, moe_sum


def _sample_row(logits_row, key, temperature):
    """Per-row sampling lane (vmapped): bit-identical to the host path's
    ``engine._sample(logits[row:row+1, 0], key, temperature)``.  The
    categorical draw consumes the same key stream as the per-request call
    (same shape [1, V], so the threefry bits coincide); greedy rows take
    the argmax and discard the draw."""
    greedy = jnp.argmax(logits_row, axis=-1)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    drawn = jax.random.categorical(key, logits_row / safe_t, axis=-1)
    return jnp.where(temperature > 0.0, drawn, greedy)        # [1]


def decode_macro_step(params, cfg: ModelConfig, kv, tables, gid_tables,
                      tokens, cur_pos, keys, iters, emitted, max_new,
                      eos_ids, temps, *, n_steps: int, page_size: int,
                      impl: Optional[str] = None, cond=None, state_cols=None,
                      mesh=None, shard=_IDENT):
    """Up to ``n_steps`` fully-paged decode steps in ONE device launch.

    A ``jax.lax.scan`` drives ``_paged_decode_core`` with on-device
    sampling (the exact per-request ``fold_in(key, i)`` schedule of
    ``engine.generate``), on-device mass accumulation, and EOS / length
    masking, so the host only uploads page tables once per movement
    period and downloads ``(tokens, summed mass, finished flags)`` once
    -- the serving hot loop never synchronises at token granularity.

    Per-row serving state (all int32[B] / f32[B] unless noted):
      keys     uint32[B, 2] raw PRNG keys (``req._key``)
      iters    decode iterations done (``req._i``: the fold_in schedule)
      emitted  tokens emitted so far incl. the prefill sample
      max_new  the request's token budget (stop when ``emitted`` reaches it)
      eos_ids  per-request EOS token (-1 = none)
      temps    sampling temperature

    A row is *alive* while ``cur_pos >= 0`` and no stop condition has
    fired; dead rows freeze completely -- no KV writes (their ``cur`` is
    -1 so the core masks them), no key folds, no mass, no emission -- so
    the emitted stream is bit-identical to the per-token path, which
    retires a request on the host before the next launch.  The stop mask
    is also evaluated at entry over the *incoming* token and budget: the
    pipelined scheduler admits rows whose prefill-sampled first token is
    still in flight, and such a row freezes before its first decode step
    if that token already hits EOS or ``max_new``.

    Returns ``(tokens_out int32[n_steps, B] (-1 = row not alive), new_kv,
    state)`` with ``state = {mass_sum f32[B, n_row_pages], alive_steps
    int32[B], pos, keys, iters, emitted, stopped bool[B], counts
    int32[B + 2]}`` -- everything the scheduler needs to retire finished
    requests and feed the monitor one merged mass per period.  ``counts``
    is ``alive_steps`` followed by the expert layers' counts summed over
    steps and layers (held token-expert pairs, held experts touched), so
    one download brings both.
    """
    b = tokens.shape[0]
    n_row_pages = tables.shape[1]

    def run(carry):
        (kv, tok, pos, ks, it, em, stopped, mass_sum, alive_steps,
         moe_sum) = carry
        alive = (pos >= 0) & ~stopped
        cur = jnp.where(alive, pos, -1)
        logits, kv, mass, moe = _paged_decode_core(
            params, cfg, kv, tables, gid_tables, tok, cur,
            page_size=page_size, impl=impl, cond=cond,
            state_cols=state_cols, mesh=mesh, shard=shard)
        mass_sum = mass_sum + mass            # core zeroes dead rows
        moe_sum = moe_sum + moe
        alive_steps = alive_steps + alive.astype(jnp.int32)
        ks2 = jax.vmap(jax.random.fold_in)(ks, it)
        new_tok = jax.vmap(_sample_row)(logits, ks2, temps)   # [B, 1]
        ks = jnp.where(alive[:, None], ks2, ks)
        it = jnp.where(alive, it + 1, it)
        em = jnp.where(alive, em + 1, em)
        tok = jnp.where(alive[:, None], new_tok.astype(tok.dtype), tok)
        stop_now = alive & ((em >= max_new)
                            | ((eos_ids >= 0) & (tok[:, 0] == eos_ids)))
        stopped = stopped | stop_now
        pos = jnp.where(alive, pos + 1, pos)
        out = jnp.where(alive, tok[:, 0], -1)
        return (kv, tok, pos, ks, it, em, stopped, mass_sum,
                alive_steps, moe_sum), out

    def body(carry, _):
        # all rows done: skip the model entirely (lax.cond executes one
        # branch at runtime, so a macro longer than the remaining work
        # costs nothing past the last live token)
        kv, tok, pos, ks, it, em, stopped, *_ = carry
        any_alive = jnp.any((pos >= 0) & ~stopped)
        return jax.lax.cond(
            any_alive, run,
            lambda c: (c, jnp.full((b,), -1, jnp.int32)), carry)

    # a row may enter with its stop condition already met: the pipelined
    # scheduler admits fresh rows with the prefill-sampled first token
    # still in flight, so the EOS / budget check the synchronous host
    # path runs at activation happens here instead.  Such a row freezes
    # before its first decode step (alive_steps 0, no KV writes, no
    # tokens); for every other caller the incoming token was already
    # host-checked and this predicate is identically False.
    em0 = jnp.asarray(emitted, jnp.int32)
    max_new = jnp.asarray(max_new, jnp.int32)
    stopped0 = ((cur_pos >= 0)
                & ((em0 >= max_new)
                   | ((eos_ids >= 0) & (tokens[:, 0] == eos_ids))))
    init = (kv, tokens, cur_pos, keys, jnp.asarray(iters, jnp.int32),
            em0, stopped0,
            jnp.zeros((b, n_row_pages), jnp.float32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((2,), jnp.int32))
    (kv, tok, pos, ks, it, em, stopped, mass_sum,
     alive_steps, moe_sum), toks_out = jax.lax.scan(body, init, None,
                                                    length=n_steps)
    state = {"mass_sum": mass_sum, "alive_steps": alive_steps, "pos": pos,
             "keys": ks, "iters": it, "emitted": em, "stopped": stopped,
             "last_tok": tok,
             "counts": jnp.concatenate([alive_steps, moe_sum])}
    return toks_out, kv, state


def init_specs_only(cfg: ModelConfig):
    """Logical-axis spec tree without allocating full-size params.

    The spec tree's structure depends only on the segment patterns and
    feature flags, never on dims -- so build it from a tiny structure twin
    of the config (same patterns/flags, toy sizes).
    """
    import dataclasses as _dc


    kv = 4 if cfg.num_heads == cfg.num_kv_heads else min(4, max(
        1, cfg.num_kv_heads))
    twin = _dc.replace(
        cfg,
        d_model=64, num_heads=4, num_kv_heads=kv, head_dim=16,
        d_ff=128 if cfg.d_ff else 0, vocab_size=64,
        segments=tuple((pat, 1) for pat, _ in cfg.segments),
        lru_width=32 if cfg.lru_width else 0,
        cond_dim=64 if cfg.cond_dim else 0,
        window_size=min(cfg.window_size, 8) if cfg.window_size else 0,
        moe=(_dc.replace(cfg.moe, num_experts=8, top_k=2, d_expert=16,
                         d_shared=16 if cfg.moe.d_shared else 0,
                         n_group=min(cfg.moe.n_group, 4),
                         topk_group=min(cfg.moe.topk_group, 2),
                         held_start=0, held=0)
             if cfg.moe else None),
        mla=(_dc.replace(cfg.mla, q_lora_rank=16, kv_lora_rank=8,
                         qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8)
             if cfg.mla else None),
        remat=False, moe_impl="dense",
    )
    return init(jax.random.PRNGKey(0), twin)[1]
