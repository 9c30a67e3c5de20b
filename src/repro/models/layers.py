"""Transformer building blocks: norms, RoPE, blockwise (flash-style) attention,
decode attention (incl. sequence-sharded flash-decode combine), GLU MLP, and a
sort-based capacity MoE layer.

Everything is a pure function over explicit param pytrees. Attention never
materializes the full (S, S) score matrix: queries are processed in chunks and
KV is scanned blockwise with an online-softmax accumulator (fp32), which is
what makes the 32k-prefill shapes compilable within HBM.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

NEG_INF = -1e30


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (..., S, H, Dh), positions: (..., S) int32."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                       # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise attention (training / prefill) — online softmax, GQA-aware
# ---------------------------------------------------------------------------

def blockwise_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
    unroll: bool = False,
) -> Array:
    """q: (B, Sq, Hq, Dh), k/v: (B, Skv, Hkv, Dh) with Hq % Hkv == 0.

    Flash-style: scan over KV chunks keeping running (max, sum, acc) in fp32.
    q_offset: absolute position of q[0] (for chunked prefill / decode windows).
    """
    B, Sq, Hq, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    groups = Hq // Hkv
    scale = 1.0 / np.sqrt(Dh)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0

    # reshape to grouped heads: (B, S, Hkv, G, Dh)
    qg = q.reshape(B, Sq, Hkv, groups, Dh)

    def one_q_chunk(qc, qpos0):
        # qc: (B, Cq, Hkv, G, Dh)
        def kv_step(carry, inputs):
            m, l, acc = carry
            kc, vc, kpos0 = inputs  # (B, Ck, Hkv, Dh)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = qpos0 + jnp.arange(qc.shape[1]) + q_offset
                kpos = kpos0 + jnp.arange(kc.shape[1])
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        Cq = qc.shape[1]
        m0 = jnp.full((B, Cq, Hkv, groups), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Cq, Hkv, groups), jnp.float32)
        a0 = jnp.zeros((B, Cq, Hkv, groups, Dh), jnp.float32)
        n_kv = Skv // kv_chunk
        ks = k.reshape(B, n_kv, kv_chunk, Hkv, Dh).swapaxes(0, 1)
        vs = v.reshape(B, n_kv, kv_chunk, Hkv, Dh).swapaxes(0, 1)
        kpos = jnp.arange(n_kv) * kv_chunk
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, kpos),
                                      unroll=n_kv if unroll else 1)
        return acc / jnp.maximum(l, 1e-20)[..., None]

    n_q = Sq // q_chunk
    if n_q == 1:
        out = one_q_chunk(qg, jnp.int32(0)).reshape(B, Sq, Hq, Dh)
        return out.astype(q.dtype)
    qs = qg.reshape(B, n_q, q_chunk, Hkv, groups, Dh).swapaxes(0, 1)
    qpos0 = jnp.arange(n_q) * q_chunk
    out = jax.lax.map(lambda args: one_q_chunk(*args), (qs, qpos0))
    out = out.swapaxes(0, 1).reshape(B, Sq, Hq, Dh)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention — one new token against a KV cache
# ---------------------------------------------------------------------------

def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     cache_len: Array) -> Array:
    """q: (B, Hq, Dh); caches: (B, S, Hkv, Dh); cache_len: (B,) valid length.

    O(S) per token — naturally sub-quadratic; this is the ``decode_*`` /
    ``long_500k`` path (DESIGN.md §4 note).
    """
    B, S, Hkv, Dh = k_cache.shape
    Hq = q.shape[1]
    groups = Hq // Hkv
    qg = q.reshape(B, Hkv, groups, Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) / np.sqrt(Dh)
    mask = jnp.arange(S)[None, :] < cache_len[:, None]       # (B, S)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, Dh).astype(q.dtype)


def decode_attention_partial(q: Array, k_shard: Array, v_shard: Array,
                             valid: Array) -> tuple[Array, Array, Array]:
    """Flash-decode partial on one KV sequence shard.

    Returns (o_partial (B,Hq,Dh) fp32, lse-normalizer pieces m (B,Hq), l (B,Hq))
    to be combined across shards:  global softmax = rescale-by-max + sum.
    """
    B, S, Hkv, Dh = k_shard.shape
    Hq = q.shape[1]
    groups = Hq // Hkv
    qg = q.reshape(B, Hkv, groups, Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_shard,
                   preferred_element_type=jnp.float32) / np.sqrt(Dh)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(-1)                                            # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_shard.dtype), v_shard,
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, Hq, Dh), m.reshape(B, Hq), l.reshape(B, Hq))


def combine_decode_partials(o: Array, m: Array, l: Array, axis_names) -> Array:
    """Cross-shard softmax combine (log-sum-exp trick), inside shard_map."""
    m_glob = jax.lax.pmax(m, axis_names)
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axis_names)
    o_glob = jax.lax.psum(o * corr[..., None], axis_names)
    return o_glob / jnp.maximum(l_glob, 1e-20)[..., None]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def glu_mlp(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    """SwiGLU (llama family)."""
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x: Array, w_in: Array, b_in: Array, w_out: Array,
             b_out: Array) -> Array:
    return jax.nn.gelu(x @ w_in + b_in) @ w_out + b_out


def mlp_stack(x: Array, weights: list[Array], biases: list[Array],
              act=jax.nn.relu, final_act=None) -> Array:
    """Plain MLP tower (recsys bottom/top MLPs)."""
    for i, (w, b) in enumerate(zip(weights, biases)):
        x = x @ w + b
        if i < len(weights) - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


# ---------------------------------------------------------------------------
# MoE: top-k routing + sort-based capacity dispatch (GShard semantics,
# MegaBlocks-style grouped compute, experts shardable on the bank axis)
# ---------------------------------------------------------------------------

class MoEStats(NamedTuple):
    load: Array       # (E,) routed token counts (pre-drop)
    dropped: Array    # () fraction dropped by capacity


def moe_layer(x: Array, w_router: Array, w_gate: Array, w_up: Array,
              w_down: Array, *, top_k: int, capacity_factor: float = 1.25,
              ) -> tuple[Array, MoEStats]:
    """x: (T, d). Experts: w_gate/up (E, d, ff), w_down (E, ff, d).

    Sort-based dispatch: tokens are ranked within their expert via argsort —
    avoids the (T, E, C) one-hot dispatch tensor entirely; the (E, C, d)
    buffer is the only expanded intermediate and shards over the bank axis.
    """
    T, d = x.shape
    E = w_gate.shape[0]
    probs = jax.nn.softmax(x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    gates, eidx = jax.lax.top_k(probs, top_k)                # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1)                                # (T*k,)
    tok_of = jnp.repeat(jnp.arange(T), top_k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))       # (E,)
    rank = jnp.arange(T * top_k) - starts[sorted_e]
    C = max(1, int(T * top_k * capacity_factor / E))
    keep = rank < C
    dest = jnp.where(keep, sorted_e * C + rank, E * C)       # drops -> scratch

    xs = x[tok_of[order]]                                    # (T*k, d)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].add(
        jnp.where(keep[:, None], xs, 0))
    buf = buf[:-1].reshape(E, C, d)

    h = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, w_up)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)                # (E, C, d)

    y_sorted = y.reshape(E * C, d)[jnp.where(keep, dest, 0)]
    y_sorted = jnp.where(keep[:, None], y_sorted, 0)
    y_flat = jnp.zeros((T * top_k, d), x.dtype).at[order].set(y_sorted)
    y_tok = y_flat.reshape(T, top_k, d)
    out = (y_tok * gates[..., None].astype(x.dtype)).sum(axis=1)

    load = jax.ops.segment_sum(jnp.ones_like(flat_e, jnp.float32), flat_e, E)
    dropped = 1.0 - keep.sum().astype(jnp.float32) / (T * top_k)
    return out, MoEStats(load=load, dropped=dropped)


def moe_layer_sharded(x: Array, w_router: Array, w_gate: Array, w_up: Array,
                      w_down: Array, *, top_k: int,
                      capacity_factor: float = 1.25, dist=None) -> Array:
    """Explicit expert-parallel MoE (§Perf iteration A) — shard_map over the
    bank axis with a psum combine, replacing GSPMD's inferred dispatch.

    Why: under pure GSPMD the sort/scatter dispatch of (T·k, d) activations
    against model-sharded experts lowers to repeated full all-reduces —
    ~320 GB/layer/device on the qwen3 train cell. Here every (data, model)
    device routes its LOCAL tokens to its LOCAL experts (router weights are
    replicated so routing decisions agree across banks), computes, and a
    single (T_loc, d) psum over the bank axis merges the per-bank partial
    outputs — the same partial-sum-combine dataflow as the paper's stage 3.
    ICI floor analysis: EP must move O(T_loc·d) across the expert axis;
    psum = all-gather + reduce-scatter = 2·T_loc·d·2B ≈ 0.5 GB/layer — within
    2.3x of the top-k sparse routing floor (a token touches ≤ 8 of 16 banks).

    x: (B, S, d) logical; tokens sharded over dp, experts over the bank axis.
    """
    P = jax.sharding.PartitionSpec
    dp = dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]
    bank = dist.bank_axis
    E = w_gate.shape[0]
    n_banks = dist.mesh.shape[bank]
    assert E % n_banks == 0
    E_loc = E // n_banks

    def local(xl, wr, wg, wu, wd):
        B_l, S_l, d = xl.shape
        T = B_l * S_l
        xf = xl.reshape(T, d)
        my = jax.lax.axis_index(bank)
        probs = jax.nn.softmax(
            xf.astype(jnp.float32) @ wr.astype(jnp.float32))
        gates, eidx = jax.lax.top_k(probs, top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        flat_e = eidx.reshape(-1)
        tok_of = jnp.repeat(jnp.arange(T), top_k)
        # slots routed to MY experts; foreign slots sort to the tail
        e_loc = flat_e - my * E_loc
        key = jnp.where((e_loc >= 0) & (e_loc < E_loc), e_loc, E_loc)
        order = jnp.argsort(key, stable=True)
        sorted_e = key[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E_loc))
        rank = jnp.arange(T * top_k) - starts[sorted_e]
        C = max(1, int(T * top_k * capacity_factor / E))
        keep = (sorted_e < E_loc) & (rank < C)
        dest = jnp.where(keep, sorted_e * C + rank, E_loc * C)
        # §Perf iteration A2: index-scatter dispatch — scatter token IDS into
        # the buffer slots and gather activations ONCE: the materialized
        # working set is (E_loc*C, d) (the local experts' capacity) instead
        # of (T*k, d) (every slot incl. foreign) — 12x smaller at top-8/16
        # banks.
        tok_sorted = tok_of[order]
        buf_tok = jnp.full((E_loc * C + 1,), T, jnp.int32).at[dest].set(
            jnp.where(keep, tok_sorted, T))[:-1]
        gate_sorted = gates.reshape(-1)[order]
        buf_gate = jnp.zeros((E_loc * C + 1,), jnp.float32).at[dest].set(
            jnp.where(keep, gate_sorted, 0.0))[:-1]
        xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
        buf = xf_pad[buf_tok].reshape(E_loc, C, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * C, d)
        y = y * buf_gate[:, None].astype(y.dtype)
        out = jnp.zeros((T + 1, d), xf.dtype).at[buf_tok].add(y)[:-1]
        out = jax.lax.psum(out, bank)
        return out.reshape(B_l, S_l, d)

    return jax.shard_map(
        local, mesh=dist.mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P(bank, None, None), P(bank, None, None),
                  P(bank, None, None)),
        out_specs=P(dp, None, None),
    )(x, w_router, w_gate, w_up, w_down)
