"""Transformer building blocks: norms, RoPE, chunked (flash-style) attention,
MLP variants, and sorted-grouped-GEMM MoE.

All functions are pure; activations flow in bf16 with f32 softmax/norm
statistics.  Sharding is expressed through logical-axis constraints
(:mod:`repro.distributed.sharding`) so the same code runs unsharded on CPU
smoke tests and fully sharded on the production mesh.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

ACT_DTYPE = jnp.bfloat16


def quantize_kv(x: jax.Array):
    """Per-vector int8 quantization over the last (head) dim: (q, scale)."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, s: jax.Array, dtype=ACT_DTYPE) -> jax.Array:
    return (q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]).astype(dtype)


def cast_tree(p, dtype=ACT_DTYPE):
    """Cast float params to the activation dtype (compute-dtype cast)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, p
    )


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (full / partial a.k.a. chatglm "2d")
# ---------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, dim: int, base: float = 10000.0) -> jax.Array:
    half = dim // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs  # (..., half)


def apply_rope(x: jax.Array, positions: jax.Array, variant: str = "full") -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,). variant partial rotates hd/2."""
    if variant == "none":
        return x
    B, S, H, hd = x.shape
    rot_dim = hd if variant == "full" else hd // 2
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (B, S))
    ang = _rope_angles(positions, rot_dim)  # (B, S, rot_dim/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    xr = x[..., :rot_dim]
    x1, x2 = xr[..., : rot_dim // 2], xr[..., rot_dim // 2 :]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rot_dim == hd:
        return rotated
    return jnp.concatenate([rotated, x[..., rot_dim:]], axis=-1)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — bounded memory at 32k+ sequence lengths
# ---------------------------------------------------------------------------

def _chunk_sizes(S: int, T: int, q_chunk: int, kv_chunk: int):
    Qc = min(q_chunk, S)
    while S % Qc:
        Qc //= 2
    Kc = min(kv_chunk, T)
    while T % Kc:
        Kc //= 2
    return Qc, Kc


def _mask(qpos, kpos, causal: bool, window: int):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def _flash_fwd_impl(qg, kk, vv, causal, window, q_offset, Qc, Kc):
    """qg: (B,KV,g,S,hd); kk/vv: (B,KV,T,hd) -> (out, lse) with out like qg."""
    B, KV, g, S, hd = qg.shape
    T = kk.shape[2]
    nq, nk = S // Qc, T // Kc
    scale = 1.0 / math.sqrt(hd)
    q_pos0 = jnp.arange(Qc, dtype=jnp.int32)
    k_pos0 = jnp.arange(Kc, dtype=jnp.int32)

    def q_step(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * Qc, Qc, axis=3)
        qpos = q_pos0 + qi * Qc + q_offset

        def kv_step(carry, ki):
            acc, m, l = carry
            kc = jax.lax.dynamic_slice_in_dim(kk, ki * Kc, Kc, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vv, ki * Kc, Kc, axis=2)
            s = jnp.einsum(
                "bkgqh,bkth->bkgqt", qc, kc, preferred_element_type=jnp.float32
            ) * scale
            msk = _mask(qpos, k_pos0 + ki * Kc, causal, window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum(
                "bkgqt,bkth->bkgqh", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            return (acc * corr[..., None] + pv, m_new, l_new), None

        acc0 = jnp.zeros((B, KV, g, Qc, hd), jnp.float32)
        m0 = jnp.full((B, KV, g, Qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, g, Qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nk))
        lsafe = jnp.maximum(l, 1e-30)
        out = acc / lsafe[..., None]
        lse = m + jnp.log(lsafe)
        return None, (out.astype(qg.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, KV, g, S, hd)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, KV, g, S)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qg, kk, vv, causal, window, q_offset, Qc, Kc):
    out, _ = _flash_fwd_impl(qg, kk, vv, causal, window, q_offset, Qc, Kc)
    return out


def _flash_fwd(qg, kk, vv, causal, window, q_offset, Qc, Kc):
    out, lse = _flash_fwd_impl(qg, kk, vv, causal, window, q_offset, Qc, Kc)
    return out, (qg, kk, vv, out, lse)


def _flash_bwd(causal, window, q_offset, Qc, Kc, res, dout):
    """FlashAttention-style backward: recompute p blockwise; residuals are
    only (q, k, v, out, lse) — never the (S, T) score matrix."""
    qg, kk, vv, out, lse = res
    B, KV, g, S, hd = qg.shape
    T = kk.shape[2]
    nq, nk = S // Qc, T // Kc
    scale = 1.0 / math.sqrt(hd)
    q_pos0 = jnp.arange(Qc, dtype=jnp.int32)
    k_pos0 = jnp.arange(Kc, dtype=jnp.int32)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # (B,KV,g,S)

    def q_step(carry, qi):
        dk_acc, dv_acc = carry
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * Qc, Qc, axis=3)
        doc = jax.lax.dynamic_slice_in_dim(dout, qi * Qc, Qc, axis=3).astype(jnp.float32)
        lsec = jax.lax.dynamic_slice_in_dim(lse, qi * Qc, Qc, axis=3)
        dc = jax.lax.dynamic_slice_in_dim(delta, qi * Qc, Qc, axis=3)
        qpos = q_pos0 + qi * Qc + q_offset

        def kv_step(carry_in, ki):
            dq_c, dk_a, dv_a = carry_in
            kc = jax.lax.dynamic_slice_in_dim(kk, ki * Kc, Kc, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vv, ki * Kc, Kc, axis=2)
            s = jnp.einsum(
                "bkgqh,bkth->bkgqt", qc, kc, preferred_element_type=jnp.float32
            ) * scale
            msk = _mask(qpos, k_pos0 + ki * Kc, causal, window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            p = jnp.exp(s - lsec[..., None])  # (B,KV,g,Qc,Kc)
            dv_blk = jnp.einsum("bkgqt,bkgqh->bkth", p, doc)
            dp = jnp.einsum("bkgqh,bkth->bkgqt", doc, vc.astype(jnp.float32))
            ds = p * (dp - dc[..., None]) * scale
            dq_blk = jnp.einsum("bkgqt,bkth->bkgqh", ds, kc.astype(jnp.float32))
            dk_blk = jnp.einsum("bkgqt,bkgqh->bkth", ds, qc.astype(jnp.float32))
            dk_a = jax.lax.dynamic_update_slice_in_dim(
                dk_a, jax.lax.dynamic_slice_in_dim(dk_a, ki * Kc, Kc, axis=2) + dk_blk,
                ki * Kc, axis=2)
            dv_a = jax.lax.dynamic_update_slice_in_dim(
                dv_a, jax.lax.dynamic_slice_in_dim(dv_a, ki * Kc, Kc, axis=2) + dv_blk,
                ki * Kc, axis=2)
            return (dq_c + dq_blk, dk_a, dv_a), None

        dq0 = jnp.zeros((B, KV, g, Qc, hd), jnp.float32)
        (dq_c, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), jnp.arange(nk)
        )
        return (dk_acc, dv_acc), dq_c

    dkv0 = (jnp.zeros((B, KV, T, hd), jnp.float32), jnp.zeros((B, KV, T, hd), jnp.float32))
    (dk, dv), dqs = jax.lax.scan(q_step, dkv0, jnp.arange(nq))
    dq = dqs.transpose(1, 2, 3, 0, 4, 5).reshape(B, KV, g, S, hd)
    return dq.astype(qg.dtype), dk.astype(kk.dtype), dv.astype(vv.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, T, KV, hd)
    v: jax.Array,  # (B, T, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,          # 0 = full; else sliding-window attention
    q_offset: int = 0,        # absolute position of q[0] (prefill continuation)
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention with (Qc × Kc) tiles; GQA via head grouping.

    The (B, H, S, T) score matrix is never materialized in either pass —
    the custom VJP recomputes probability tiles blockwise (FlashAttention
    backward).  Peak extra memory is O(B·H·Qc·Kc) per step.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    g = H // KV
    Qc, Kc = _chunk_sizes(S, T, q_chunk, kv_chunk)
    qg = q.reshape(B, S, KV, g, hd).transpose(0, 2, 3, 1, 4)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    out = _flash(qg, kk, vv, causal, window, q_offset, Qc, Kc)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def decode_attention(
    q: jax.Array,        # (B, H, hd) single new token
    k_cache: jax.Array,  # (B, W, KV, hd) (ring buffer when window)
    v_cache: jax.Array,
    pos: jax.Array,      # scalar int32: absolute position of the new token
    *,
    window: int = 0,
) -> jax.Array:
    B, W, KV, hd = k_cache.shape
    H = q.shape[1]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, g, hd)
    s = jnp.einsum(
        "bkgh,bwkh->bkgw", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    slot = jnp.arange(W, dtype=jnp.int32)
    if window:
        # slot w holds absolute position p = pos - ((pos - w) mod W), valid if p >= 0
        p = pos - jnp.mod(pos - slot, W)
        valid = (p >= 0) & (p <= pos)
    else:
        valid = slot <= pos
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgw,bwkh->bkgh", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_apply(x: jax.Array, p: dict, act: str) -> jax.Array:
    if act == "swiglu":
        a = jnp.einsum("bsd,df->bsf", x, p["wi0"])
        b = jnp.einsum("bsd,df->bsf", x, p["wi1"])
        h = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * b
    elif act == "sq_relu":
        a = jnp.einsum("bsd,df->bsf", x, p["wi0"])
        r = jnp.maximum(a, 0)
        h = r * r
    else:  # gelu
        a = jnp.einsum("bsd,df->bsf", x, p["wi0"])
        h = jax.nn.gelu(a.astype(jnp.float32)).astype(x.dtype)
    h = constrain(h, "batch", None, "tp")
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# ---------------------------------------------------------------------------
# MoE: top-k routing + sort-based grouped GEMM (capacity-dropped)
# ---------------------------------------------------------------------------

def _moe_expert_compute(xe, p_wi0, p_wi1, p_wo, act, dtype):
    if act == "swiglu":
        a = jnp.einsum("ecd,edf->ecf", xe, p_wi0)
        b = jnp.einsum("ecd,edf->ecf", xe, p_wi1)
        h = jax.nn.silu(a.astype(jnp.float32)).astype(dtype) * b
    else:
        a = jnp.einsum("ecd,edf->ecf", xe, p_wi0)
        r = jnp.maximum(a, 0)
        h = r * r
    return jnp.einsum("ecf,efd->ecd", h, p_wo)


def _moe_dispatch_compute(xt, logits, e0, E_loc, p_wi0, p_wi1, p_wo, *,
                          top_k, capacity_factor, act):
    """Route xt (T,d) to the E_loc local experts [e0, e0+E_loc); returns (T,d)
    partial outputs (zeros for tokens whose experts live elsewhere)."""
    T, d = xt.shape
    E = logits.shape[1]
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    eid = topi.reshape(-1)
    wgt = topv.reshape(-1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    C = max(int(capacity_factor * T * top_k / E), 4)
    local = (eid >= e0) & (eid < e0 + E_loc)
    le = jnp.where(local, eid - e0, E_loc)  # E_loc = drop bucket
    order = jnp.argsort(le)
    so, ts, ws = le[order], tok[order], wgt[order]
    first = jnp.searchsorted(so, so, side="left")
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (so < E_loc) & (pos < C)
    # gather-only dispatch: (E_loc, C) source-token ids, then one local gather
    ids = jnp.zeros((E_loc, C), jnp.int32).at[so, pos].set(
        jnp.where(keep, ts, 0), mode="drop")
    valid = jnp.zeros((E_loc, C), bool).at[so, pos].set(keep, mode="drop")
    xe = jnp.take(xt, ids, axis=0) * valid[..., None].astype(xt.dtype)
    ye = _moe_expert_compute(xe, p_wi0, p_wi1, p_wo, act, xt.dtype)
    back = ye[so, pos] * (ws * keep)[:, None].astype(xt.dtype)
    return jnp.zeros((T, d), xt.dtype).at[ts].add(back)


def _moe_dispatch_compute_fsharded(xt, logits, e0, E_loc, p_wi0, p_wi1, p_wo,
                                   fsdp_axes, *, top_k, capacity_factor, act):
    """Weight-stationary variant: expert matrices stay f-sharded over the
    fsdp axes; the (E_loc, C, d) partial outputs are psum'd instead.  Wins
    whenever activations ≪ weights (decode)."""
    T, d = xt.shape
    E = logits.shape[1]
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    eid = topi.reshape(-1)
    wgt = topv.reshape(-1)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    C = max(int(capacity_factor * T * top_k / E), 4)
    local = (eid >= e0) & (eid < e0 + E_loc)
    le = jnp.where(local, eid - e0, E_loc)
    order = jnp.argsort(le)
    so, ts, ws = le[order], tok[order], wgt[order]
    first = jnp.searchsorted(so, so, side="left")
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (so < E_loc) & (pos < C)
    ids = jnp.zeros((E_loc, C), jnp.int32).at[so, pos].set(
        jnp.where(keep, ts, 0), mode="drop")
    valid = jnp.zeros((E_loc, C), bool).at[so, pos].set(keep, mode="drop")
    xe = jnp.take(xt, ids, axis=0) * valid[..., None].astype(xt.dtype)
    if act == "swiglu":
        a = jnp.einsum("ecd,edf->ecf", xe, p_wi0)  # f is the LOCAL f shard
        b = jnp.einsum("ecd,edf->ecf", xe, p_wi1)
        h = jax.nn.silu(a.astype(jnp.float32)).astype(xt.dtype) * b
    else:
        a = jnp.einsum("ecd,edf->ecf", xe, p_wi0)
        r = jnp.maximum(a, 0)
        h = r * r
    ye = jnp.einsum("ecf,efd->ecd", h, p_wo)  # partial sum over local f
    for ax in fsdp_axes:
        ye = jax.lax.psum(ye, ax)
    back = ye[so, pos] * (ws * keep)[:, None].astype(xt.dtype)
    return jnp.zeros((T, d), xt.dtype).at[ts].add(back)


def _moe_mode_auto(T_local: int, top_k: int, E: int, f: int, cf: float) -> str:
    """ws vs ag by napkin math (§Perf H1): per layer, ws moves ~2 psums of the
    (E_loc, C, d) partials (fwd+bwd) while ag moves the n_mats·(E_loc,d,f)
    expert weights.  Per-expert: ws ∝ 4·C·d·B_act, ag ∝ 3·d·f·B_w —
    choose ws when C < ~0.75·f."""
    import os

    forced = os.environ.get("REPRO_MOE_MODE")
    if forced in ("ws", "ag"):
        return forced
    C = max(cf * T_local * top_k / E, 4)
    return "ws" if C < 0.75 * f else "ag"


def moe_apply(
    x: jax.Array,        # (B, S, d)
    p: dict,             # router (d,E), wi0/wi1 (E,d,f), wo (E,f,d)
    *,
    top_k: int,
    capacity_factor: float,
    act: str,
    mode: str = "auto",  # auto | ag (weight all-gather) | ws (weight stationary)
) -> jax.Array:
    """Top-k MoE.  Without a mesh: single local dispatch over all experts.

    With a mesh: **expert-parallel shard_map** — activations are replicated
    across the ``model`` axis (they are only batch-sharded), each model
    column routes its tokens to its E/tp resident experts with a *local*
    gather (never a cross-shard scatter, which XLA's SPMD partitioner would
    replicate at (E,C,d) scale), computes, and the per-column partial token
    outputs are ``psum``'d over ``model``.

    Two treatments of the FSDP-sharded expert-weight dim (§Perf H1):
      * ``ag`` — all-gather weights over the fsdp axes (ZeRO-3; best when
        tokens ≫ weights, i.e. train/prefill),
      * ``ws`` — keep weights f-sharded, psum the small (E_loc, C, d)
        partials (best for decode, where per-step tokens are tiny and the
        per-layer weight all-gather dominated the collective term).
    ``auto`` picks by global token count.
    """
    from repro.distributed.sharding import get_mesh, rules

    B, S, d = x.shape
    E = p["router"].shape[1]
    mesh = get_mesh()
    wi1 = p.get("wi1", p["wi0"])  # unused when act != swiglu
    if mesh is None or "model" not in mesh.axis_names:
        xt = x.reshape(B * S, d)
        logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
        out = _moe_dispatch_compute(
            xt, logits, 0, E, p["wi0"], wi1, p["wo"],
            top_k=top_k, capacity_factor=capacity_factor, act=act)
        return out.reshape(B, S, d)

    from jax.sharding import PartitionSpec as P

    r = rules()
    fsdp = r.fsdp
    batch = r.batch
    tp_n = 1
    n_batch_shards = 1
    for a, sz in zip(mesh.axis_names, mesh.devices.shape):
        if a == "model":
            tp_n = sz
        if a in batch:
            n_batch_shards *= sz
    E_loc = E // tp_n
    if mode == "auto":
        f = p["wi0"].shape[-1]
        mode = _moe_mode_auto(B * S // max(n_batch_shards, 1), top_k, E, f,
                              capacity_factor)

    def local(x_loc, router_loc, wi0_loc, wi1_loc, wo_loc):
        router_f = router_loc
        for ax in fsdp:
            router_f = jax.lax.all_gather(router_f, ax, axis=0, tiled=True)
        Bl, Sl, _ = x_loc.shape
        xt = x_loc.reshape(Bl * Sl, d)
        logits = jnp.einsum("td,de->te", xt, router_f).astype(jnp.float32)
        e0 = jax.lax.axis_index("model") * E_loc
        if mode == "ws":
            out = _moe_dispatch_compute_fsharded(
                xt, logits, e0, E_loc, wi0_loc, wi1_loc, wo_loc, fsdp,
                top_k=top_k, capacity_factor=capacity_factor, act=act)
        else:
            wi0_f, wi1_f, wo_f = wi0_loc, wi1_loc, wo_loc
            for ax in fsdp:
                wi0_f = jax.lax.all_gather(wi0_f, ax, axis=1, tiled=True)
                wi1_f = jax.lax.all_gather(wi1_f, ax, axis=1, tiled=True)
                wo_f = jax.lax.all_gather(wo_f, ax, axis=2, tiled=True)
            out = _moe_dispatch_compute(
                xt, logits, e0, E_loc, wi0_f, wi1_f, wo_f,
                top_k=top_k, capacity_factor=capacity_factor, act=act)
        out = jax.lax.psum(out, "model")
        return out.reshape(Bl, Sl, d)

    bspec = P(batch if batch else None, None, None)
    if mode == "ws":
        # weights stay sharded: E over model, f over fsdp axes
        wi_spec = P("model", None, fsdp if fsdp else None)
        wo_spec = P("model", fsdp if fsdp else None, None)
    else:
        wi_spec = P("model", fsdp if fsdp else None, None)
        wo_spec = P("model", None, fsdp if fsdp else None)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(
            bspec,
            P(fsdp if fsdp else None, None),
            wi_spec, wi_spec, wo_spec,
        ),
        out_specs=bspec,
        check_vma=False,
    )(x, p["router"], p["wi0"], wi1, p["wo"])
    return out
