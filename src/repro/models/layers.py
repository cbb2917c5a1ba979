"""Shared neural-net substrate (no flax/optax in this environment — built
from scratch): initializers, norms, RoPE, GQA attention (causal / sliding
window / qk-norm), GLU MLPs, and GShard-style MoE with top-k routing.

All modules are (init, apply) pairs over plain dict pytrees.  Compute dtype
is bf16 with fp32 params and fp32 softmax/normalizer math (production LM
defaults); attention dispatches to the Pallas flash kernel on TPU and to a
memory-bounded chunked online-softmax scan elsewhere (same math, same FLOPs
— see DESIGN.md).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


Params = dict[str, Any]
COMPUTE_DTYPE = jnp.bfloat16

# Cost-exact mode (launch/dryrun.py): XLA cost analysis counts a scan body
# ONCE, not x trip-count, so the dry-run lowers small fully-unrolled variants
# and extrapolates.  These globals let it force unrolling / tile sizing
# without touching the production scan path.
SCAN_UNROLL: bool | int = 1          # passed to lax.scan(unroll=...)
ATTN_CHUNK_OVERRIDE: int | None = None
MOE_SHARDMAP: bool = True            # combine-before-reduce TP expert block


def shard_hint(x: jax.Array, *dims) -> jax.Array:
    """with_sharding_constraint against the ambient mesh, if any.

    ``dims`` entries: "dp" -> the mesh's pure data-parallel axes,
    "model" -> the model axis, None -> unconstrained.  No-op outside a mesh
    context (unit tests, single-device runs).
    """
    from jax.sharding import PartitionSpec
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.thread_resources.env.physical_mesh
    if mesh.empty:
        return x
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names) or None

    def resolve(d):
        if d == "dp":
            return dp
        if d == "model":
            return "model" if "model" in names else None
        return d

    spec = PartitionSpec(*[resolve(d) for d in dims])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except ValueError:
        return x  # inside shard_map (manual axes): already shard-local


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, scale: float | None = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale)


def embed_init(key, vocab: int, d: int) -> jax.Array:
    return jax.random.normal(key, (vocab, d), jnp.float32) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: Params, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 1e6) -> jax.Array:
    """x: [..., S, H, Dh]; positions: [..., S] (broadcastable)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                                 # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qk_norm: bool = False) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, d_model, n_heads * head_dim),
        "wk": dense_init(k2, d_model, n_kv * head_dim),
        "wv": dense_init(k3, d_model, n_kv * head_dim),
        "wo": dense_init(k4, n_heads * head_dim, d_model,
                         scale=1.0 / math.sqrt(n_heads * head_dim)),
    }
    if qk_norm:
        p["q_norm"] = norm_init(head_dim, "rmsnorm")
        p["k_norm"] = norm_init(head_dim, "rmsnorm")
    return p


def _chunked_attention(q, k, v, *, causal: bool, window: int | None,
                       q_chunk: int = 1024, kv_chunk: int = 1024) -> jax.Array:
    """Online-softmax attention in pure XLA: flash math, O(S·chunk) memory.

    q: [B, Hq, Sq, Dh]; k/v: [B, Hkv, Skv, Dh] with Hq % Hkv == 0.
    Used off-TPU and as the kernel's semantics reference at model level.
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, dh)
    scale = dh ** -0.5
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    nq = -(-sq // qc)
    nk = -(-skv // kc)
    sq_pad, skv_pad = nq * qc, nk * kc
    qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, sq_pad - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0)))
    q_off = skv - sq  # causal offset: query i attends to kv <= i + q_off

    def q_block(carry, qi):
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * qc, qc, axis=3)  # [B,Hkv,G,qc,Dh]

        def kv_block(acc, kj):
            m_run, l_run, o_run = acc
            kb = jax.lax.dynamic_slice_in_dim(kp, kj * kc, kc, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(vp, kj * kc, kc, axis=2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            qpos = qi * qc + jnp.arange(qc)[:, None] + q_off
            kpos = kj * kc + jnp.arange(kc)[None, :]
            mask = kpos < skv
            if causal:
                mask &= qpos >= kpos
            if window is not None:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, -1e30)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_run, m_cur)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            o_new = o_run * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, o_new), ()

        init = (jnp.full((b, hkv, group, qc), -1e30, jnp.float32),
                jnp.zeros((b, hkv, group, qc), jnp.float32),
                jnp.zeros((b, hkv, group, qc, dh), jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_block, init, jnp.arange(nk),
                                    unroll=SCAN_UNROLL)
        l = jnp.where(l == 0.0, 1.0, l)
        return carry, (o / l[..., None]).astype(q.dtype)

    _, out = jax.lax.scan(q_block, (), jnp.arange(nq), unroll=SCAN_UNROLL)
    # out: [nq, B, Hkv, G, qc, Dh] -> [B, Hq, Sq, Dh]
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, group, sq_pad, dh)[:, :, :, :sq]
    return out.reshape(b, hq, sq, dh)


def attention_apply(p: Params, x: jax.Array, positions: jax.Array, *,
                    n_heads: int, n_kv: int, head_dim: int,
                    causal: bool = True, window: int | None = None,
                    qk_norm: bool = False, rope_theta: float = 1e6,
                    cache: tuple | None = None, cache_pos=None) -> tuple:
    """x: [B, S, D].  If ``cache`` is given (decode), returns updated cache.

    cache = (k_cache, v_cache): [B, C, n_kv, Dh]; cache_pos: int32 scalar —
    absolute position of the incoming token(s); ring-buffered when C < pos.
    """
    b, s, _ = x.shape
    xc = x.astype(COMPUTE_DTYPE)
    q = (xc @ p["wq"].astype(COMPUTE_DTYPE)).reshape(b, s, n_heads, head_dim)
    k = (xc @ p["wk"].astype(COMPUTE_DTYPE)).reshape(b, s, n_kv, head_dim)
    v = (xc @ p["wv"].astype(COMPUTE_DTYPE)).reshape(b, s, n_kv, head_dim)
    if qk_norm:
        q = norm_apply(p["q_norm"], q, "rmsnorm")
        k = norm_apply(p["k_norm"], k, "rmsnorm")
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)

    if cache is None:
        qh = jnp.moveaxis(q, 2, 1)          # [B, Hq, S, Dh]
        kh = jnp.moveaxis(k, 2, 1)
        vh = jnp.moveaxis(v, 2, 1)
        # Sequence-parallel attention when heads don't divide the model axis
        # (36H/40H/8H on a 16-way mesh): XLA otherwise re-shards the head dim
        # with per-layer all-gathers measured at TBs/step (EXPERIMENTS §Perf).
        # Queries/outputs shard S on "model"; K/V replicate over "model" (one
        # small GQA KV all-gather per layer).
        from jax._src import mesh as mesh_lib
        amesh = mesh_lib.thread_resources.env.physical_mesh
        msize = amesh.shape.get("model", 0) if not amesh.empty else 0
        # Long sequences only: at 32k the head-resharding all-gathers dominate
        # (18x measured); at 4k train shapes the hint instead amplifies
        # backward-pass resharding (2.4x WORSE, measured) — see §Perf log.
        seq_parallel = (msize > 1 and n_heads % msize != 0
                        and s % msize == 0 and s >= 16384)
        if seq_parallel:
            qh = shard_hint(qh, "dp", None, "model", None)
            kh = shard_hint(kh, "dp", None, None, None)
            vh = shard_hint(vh, "dp", None, None, None)
        if ATTN_CHUNK_OVERRIDE is not None:
            out = _chunked_attention(qh, kh, vh, causal=causal, window=window,
                                     q_chunk=ATTN_CHUNK_OVERRIDE,
                                     kv_chunk=ATTN_CHUNK_OVERRIDE)
        elif jax.default_backend() == "tpu" and s >= 512:
            from ..kernels import ops as kernel_ops
            group = n_heads // n_kv
            kr = jnp.repeat(kh, group, axis=1)
            vr = jnp.repeat(vh, group, axis=1)
            out = kernel_ops.flash_attention(
                qh.reshape(b * n_heads, s, head_dim),
                kr.reshape(b * n_heads, s, head_dim),
                vr.reshape(b * n_heads, s, head_dim),
                causal=causal, window=window).reshape(b, n_heads, s, head_dim)
        else:
            out = _chunked_attention(qh, kh, vh, causal=causal, window=window)
        out = jnp.moveaxis(out, 1, 2).reshape(b, s, n_heads * head_dim)
        new_cache = None
    else:
        k_cache, v_cache = cache
        c = k_cache.shape[1]
        slot = (cache_pos % c).astype(jnp.int32)  # ring buffer (SWA windows)
        k_cache = k_cache.at[:, slot].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[:, slot].set(v[:, 0].astype(v_cache.dtype))
        # decode attention (q_len == 1): HBM-bound gather math in fp32
        kv_pos_abs = cache_pos - ((slot - jnp.arange(c)) % c)  # abs position per ring slot
        valid = (kv_pos_abs >= 0) & (kv_pos_abs <= cache_pos)
        if window is not None:
            valid &= (cache_pos - kv_pos_abs) < window
        group = n_heads // n_kv
        qg = q.reshape(b, n_heads, head_dim).reshape(b, n_kv, group, head_dim)
        scores = jnp.einsum("bkgd,bckd->bkgc", qg.astype(jnp.float32),
                            k_cache.astype(jnp.float32)) * head_dim ** -0.5
        scores = jnp.where(valid[None, None, None, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgc,bckd->bkgd", w, v_cache.astype(jnp.float32))
        out = out.reshape(b, 1, n_heads * head_dim).astype(COMPUTE_DTYPE)
        new_cache = (k_cache, v_cache)

    out = out.astype(COMPUTE_DTYPE) @ p["wo"].astype(COMPUTE_DTYPE)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, kind: str) -> Params:
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], d_model, d_ff),
                "w_up": dense_init(ks[1], d_model, d_ff),
                "w_down": dense_init(ks[2], d_ff, d_model, scale=1.0 / math.sqrt(d_ff))}
    return {"w_up": dense_init(ks[0], d_model, d_ff),
            "w_down": dense_init(ks[1], d_ff, d_model, scale=1.0 / math.sqrt(d_ff))}


def mlp_apply(p: Params, x: jax.Array, kind: str) -> jax.Array:
    xc = x.astype(COMPUTE_DTYPE)
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        g = act(xc @ p["w_gate"].astype(COMPUTE_DTYPE))
        u = xc @ p["w_up"].astype(COMPUTE_DTYPE)
        return (g * u) @ p["w_down"].astype(COMPUTE_DTYPE)
    h = jax.nn.gelu(xc @ p["w_up"].astype(COMPUTE_DTYPE))
    return h @ p["w_down"].astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style dispatch; EP or TP sharding via pjit)
# ---------------------------------------------------------------------------

def _expert_block_dispatch(fn, dest, updates, gates, w, n_experts: int):
    """Run the expert block in pjit-land, or — when expert weights are
    TP-sharded on d_ff (experts don't divide the model axis) — per-shard via
    shard_map so the cross-shard reduction happens AFTER the combine and in
    bf16.  pjit places the psum on the dispatched [B,E,cap,D] f32 buffer
    (measured 2.68 GB/layer on mixtral); combining first shrinks it to the
    [B,S,D] bf16 output (5x fewer wire bytes; EXPERIMENTS §Perf)."""
    from jax.sharding import PartitionSpec as P_
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.thread_resources.env.physical_mesh
    msize = mesh.shape["model"] if (not mesh.empty and "model" in mesh.axis_names) else 0
    if msize == 0 or n_experts % msize == 0 or not MOE_SHARDMAP:
        # no mesh (tests/CPU) or clean EP sharding: pjit handles it well
        return fn(dest, updates, gates, w)

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None
    dp_size = 1
    for a in (dp or ()):
        dp_size *= mesh.shape[a]
    if dest.shape[0] % dp_size != 0:
        # batch not divisible over the DP axes (e.g. long-context batch=1):
        # replicate batch inside shard_map instead
        dp = None

    def local_fn(dest, updates, gates, w):
        out_partial = fn(dest, updates, gates, w)        # bf16, combined
        return jax.lax.psum(out_partial, "model")

    w_specs = {k: (P_(None, "model", None) if k == "w_down"
                   else P_(None, None, "model")) for k in w}
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P_(dp, None), P_(dp, None, None), P_(dp, None), w_specs),
        out_specs=P_(dp, None, None), check_vma=False,
    )(dest, updates, gates, w)


def moe_init(key, d_model: int, d_ff: int, n_experts: int, kind: str) -> Params:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)

    def stack(k, din, dout, scale):
        return jax.random.normal(k, (n_experts, din, dout), jnp.float32) * scale

    p = {"router": dense_init(kr, d_model, n_experts, scale=0.02)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = stack(k1, d_model, d_ff, scale_in)
        p["w_up"] = stack(k2, d_model, d_ff, scale_in)
        p["w_down"] = stack(k3, d_ff, d_model, scale_out)
    else:
        p["w_up"] = stack(k1, d_model, d_ff, scale_in)
        p["w_down"] = stack(k2, d_ff, d_model, scale_out)
    return p


def moe_apply(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
              kind: str, capacity_factor: float = 1.25) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out, aux_loss).

    Scatter/gather dispatch + dense [E, C, D] expert einsums.  The textbook
    GShard one-hot dispatch einsum costs O(T·E·C·D) — at 1M tokens it
    dominates the entire step by >10x (measured in the dry-run; EXPERIMENTS
    §Perf) — so routing is done with O(T·K·D) scatter/gather instead while
    keeping the dense expert compute that pjit shards cleanly on the expert
    (EP) or d_ff (TP) axis."""
    b, s, d = x.shape
    tk = s * top_k
    xc = x.astype(COMPUTE_DTYPE)                                            # [B, S, D]
    logits = jnp.einsum("bsd,de->bse", xc,
                        p["router"].astype(COMPUTE_DTYPE)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)                       # [B, S, K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Per-group dispatch (GShard §3.2 "groups"): capacity and queue positions
    # are computed within each batch row, never across the global token axis.
    # A global cumsum makes the scatter destination depend on remote tokens,
    # which forces XLA to replicate the dispatch buffer over the data axis —
    # measured 14-16x redundant expert compute in the dry-run (EXPERIMENTS
    # §Perf).  Per-row routing keeps B a scatter batch dim, so the expert
    # batch stays data-sharded.
    cap = max(1, -(-int(capacity_factor * s * top_k) // n_experts))
    onehot = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.int32)           # [B, S, K, E]
    flat = onehot.reshape(b, tk, n_experts)
    pos = (jnp.cumsum(flat, axis=1) - flat)                                 # [B, TK, E]
    pos = jnp.sum(pos * flat, axis=-1)                                      # [B, TK]
    idx_flat = gate_idx.reshape(b, tk)
    keep = pos < cap
    dest = jnp.where(keep, idx_flat * cap + pos, n_experts * cap)           # [B, TK]
    src = jnp.arange(tk, dtype=jnp.int32) // top_k

    def row_scatter(dest_r, upd_r):
        buf = jnp.zeros((n_experts * cap, d), COMPUTE_DTYPE)
        return buf.at[dest_r].add(upd_r, mode="drop")

    gates = gate_vals.reshape(b, tk).astype(COMPUTE_DTYPE)
    gates = jnp.where(keep, gates, 0)

    def expert_block(dest, xin, gates, w):
        """scatter-dispatch -> expert matmuls -> gather-combine; [B,S,D] in
        and out.  The TK-expansion gather happens *inside* so that, on the
        shard_map TP path, both the forward psum (output) and the backward
        psum (dL/dx) are S-sized bf16 tensors — passing the expanded [B,TK,D]
        in instead makes the backward all-reduce K x larger (measured;
        EXPERIMENTS §Perf)."""
        bl = dest.shape[0]
        updates = xin[:, src, :]                                            # [B,TK,D]
        xe = jax.vmap(row_scatter)(dest, updates).reshape(bl, n_experts, cap, d)
        xe = shard_hint(xe, "dp", None, None, None)
        if kind in ("swiglu", "geglu"):
            act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
            g = act(jnp.einsum("becd,edf->becf", xe, w["w_gate"].astype(COMPUTE_DTYPE)))
            u = jnp.einsum("becd,edf->becf", xe, w["w_up"].astype(COMPUTE_DTYPE))
            ye = jnp.einsum("becf,efd->becd", g * u, w["w_down"].astype(COMPUTE_DTYPE))
        else:
            h = jax.nn.gelu(jnp.einsum("becd,edf->becf", xe,
                                       w["w_up"].astype(COMPUTE_DTYPE)))
            ye = jnp.einsum("becf,efd->becd", h, w["w_down"].astype(COMPUTE_DTYPE))
        # gather combine (per row): out = sum_k gate * ye[dest]
        ye_flat = ye.reshape(bl, n_experts * cap, d)
        got = jnp.take_along_axis(ye_flat,
                                  jnp.minimum(dest, n_experts * cap - 1)[..., None],
                                  axis=1)                                   # [B,TK,D]
        got = got * gates[..., None]
        return got.reshape(bl, s, top_k, d).sum(axis=2)

    w = {k2: p[k2] for k2 in p if k2.startswith("w_")}
    out = _expert_block_dispatch(expert_block, dest, xc, gates, w, n_experts)

    # load-balance aux loss (Switch): E * sum_e (frac_tokens_e * frac_probs_e)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], n_experts, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return out, aux
