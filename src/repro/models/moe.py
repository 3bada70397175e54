"""Expert-parallel Mixture-of-Experts with capacity-based top-k dispatch.

Routing (router logits, top-k gates, load-balance aux) runs as plain SPMD
jnp -- it partitions cleanly.  Dispatch/expert-compute/combine runs inside an
explicit ``shard_map``: activations are sharded over the batch ("data")
axes and *replicated* over the "model" axis, experts are sharded over
"model", so each shard scatters its local tokens into the buffers of its
local experts with NO cross-shard traffic; a single psum over "model"
combines expert outputs.  (The naive pjit scatter forces XLA to all-reduce
the full global dispatch buffer per layer -- measured 17 TB/device/step on
arctic-480b train_4k -- which this formulation eliminates; see EXPERIMENTS
§Perf.)

Supports top-1/top-2, a shared always-on expert (llama4) and a parallel
dense residual FFN (arctic, handled at the block level).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import Px
from .config import ModelConfig
from .layers import _normal


def init_moe(key, cfg: ModelConfig):
    dt = cfg.jdtype()
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    si, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": Px(_normal(ks[0], (d, E), jnp.float32, si), (None, None)),
        "wi": Px(_normal(ks[1], (E, d, f), dt, si), ("expert", "fsdp", None)),
        "wg": Px(_normal(ks[2], (E, d, f), dt, si), ("expert", "fsdp", None)),
        "wo": Px(_normal(ks[3], (E, f, d), dt, so), ("expert", None, "fsdp")),
    }
    return p


def capacity(tokens: int, cfg: ModelConfig) -> int:
    """Per-expert slot count for ``tokens`` routed tokens."""
    c = math.ceil(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    c = max(1, c)
    if c > 8:
        c += (-c) % 8
    return min(tokens * cfg.top_k, c)


def _routing(p, xf, cfg: ModelConfig):
    """(gate, idx, aux) from flat tokens (T, d)."""
    E, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0) / (xf.shape[0] * k)
    aux = E * jnp.sum(me * ce)
    return gate, idx, aux


def _dispatch_compute_combine(xf, gate, idx, wi, wg, wo, *, E: int, k: int,
                              C: int, e0, E_local: int):
    """Local dispatch -> expert FFN -> combine for ``E_local`` experts
    starting at global id ``e0``.  xf: (T, d) local tokens."""
    T, d = xf.shape
    e_flat = idx.T.reshape(-1)                          # (k*T,) slot-major
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.float32)
    pos = jnp.cumsum(onehot, axis=0) - 1.0
    pos = jnp.einsum("te,te->t", pos, onehot).astype(jnp.int32)
    keep = pos < C
    rel = e_flat - e0
    mine = keep & (rel >= 0) & (rel < E_local)
    relc = jnp.clip(rel, 0, E_local - 1)
    slot = jnp.minimum(pos, C - 1)

    tok_ids = jnp.tile(jnp.arange(T), k)
    buf = jnp.zeros((E_local, C, d), xf.dtype)
    buf = buf.at[relc, slot].add(
        xf[tok_ids] * mine[:, None].astype(xf.dtype))

    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    y_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)

    y_tok = y_e[relc, slot] * mine[:, None].astype(y_e.dtype)
    gates_flat = gate.T.reshape(-1)[:, None].astype(y_tok.dtype)
    return (y_tok * gates_flat).reshape(k, T, d).sum(0)


def _dense_partial(x_l, wi, wg, wo, mlp_kind: str):
    """Column/row-parallel dense FFN on a model shard; returns the PARTIAL
    (pre-psum) output so it can share the MoE combine's all-reduce."""
    h = jnp.einsum("td,df->tf", x_l, wi)
    if wg is not None:
        h = jax.nn.silu(jnp.einsum("td,df->tf", x_l, wg)) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("tf,fd->td", h, wo)


def apply_moe(p, x, cfg: ModelConfig, rules, mlp_res=None, mlp_shared=None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).

    ``mlp_res`` / ``mlp_shared``: optional dense FFN param dicts (arctic's
    dense residual, llama4's shared expert).  When given, their partial
    outputs are summed with the MoE partial INSIDE the shard_map so the
    whole FFN sublayer costs a single (tokens, d) psum per layer
    (EXPERIMENTS §Perf: -1 activation all-reduce per layer fwd+bwd).
    """
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(b * s, d)
    xf = rules.shard(xf, "batch", None)

    ep_axis = rules.axis("expert")
    if ep_axis is None or rules.mesh is None:
        # single-shard path (smoke tests): plain local dispatch
        gate, idx, aux = _routing(p, xf, cfg)
        C = capacity(b * s, cfg)
        y = _dispatch_compute_combine(xf, gate, idx, p["wi"], p["wg"],
                                      p["wo"], E=E, k=k, C=C,
                                      e0=jnp.int32(0), E_local=E)
        for mlp_p in (mlp_res, mlp_shared):
            if mlp_p is not None:
                y = y + _dense_partial(xf, mlp_p["wi"], mlp_p.get("wg"),
                                       mlp_p["wo"], cfg.mlp)
        return y.reshape(b, s, d).astype(x.dtype), aux

    mesh = rules.mesh
    tp = mesh.shape[ep_axis]
    E_local = E // tp
    batch_ax = rules.axis("batch")
    n_batch_shards = 1
    for a in (batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)):
        if a:
            n_batch_shards *= mesh.shape[a]
    T_local = (b * s) // n_batch_shards
    C = capacity(T_local, cfg)
    fsdp_ax = rules.axis("fsdp")
    batch_axes = tuple(a for a in (batch_ax if isinstance(batch_ax, tuple)
                                   else (batch_ax,)) if a)

    n_mlps = (mlp_res is not None) + (mlp_shared is not None)

    def local(xf_l, wi_l, wg_l, wo_l, *mlps):
        # routing stays local to the data shard (no global probs tensor)
        gate_l, idx_l, aux_parts = _routing_local(p["router"], xf_l, cfg)
        if fsdp_ax is not None:
            wi_l = jax.lax.all_gather(wi_l, fsdp_ax, axis=1, tiled=True)
            wg_l = jax.lax.all_gather(wg_l, fsdp_ax, axis=1, tiled=True)
            wo_l = jax.lax.all_gather(wo_l, fsdp_ax, axis=2, tiled=True)
        e0 = jax.lax.axis_index(ep_axis) * E_local
        y_l = _dispatch_compute_combine(
            xf_l, gate_l, idx_l, wi_l, wg_l, wo_l,
            E=E, k=k, C=C, e0=e0, E_local=E_local)
        # dense residual / shared expert share the same psum
        for j in range(n_mlps):
            mwi, mwg, mwo = mlps[3 * j: 3 * j + 3]
            if fsdp_ax is not None:
                mwi = jax.lax.all_gather(mwi, fsdp_ax, axis=0, tiled=True)
                if mwg is not None:
                    mwg = jax.lax.all_gather(mwg, fsdp_ax, axis=0,
                                             tiled=True)
                mwo = jax.lax.all_gather(mwo, fsdp_ax, axis=1, tiled=True)
            y_l = y_l + _dense_partial(xf_l, mwi, mwg, mwo, cfg.mlp)
        y = jax.lax.psum(y_l.astype(xf_l.dtype), ep_axis)
        # aux load-balance loss: (E,)-sized stats reduced over data shards
        me_sum, ce_cnt, n_tok = aux_parts
        if batch_axes:
            me_sum = jax.lax.psum(me_sum, batch_axes)
            ce_cnt = jax.lax.psum(ce_cnt, batch_axes)
            n_tok = jax.lax.psum(n_tok, batch_axes)
        me = me_sum / n_tok
        ce = ce_cnt / (n_tok * cfg.top_k)
        aux = E * jnp.sum(me * ce)
        return y, aux

    tok_spec = P(batch_ax, None)
    mlp_args = []
    mlp_specs = []
    for mlp_p in (mlp_res, mlp_shared):
        if mlp_p is not None:
            mlp_args += [mlp_p["wi"], mlp_p.get("wg"), mlp_p["wo"]]
            mlp_specs += [P(fsdp_ax, ep_axis), P(fsdp_ax, ep_axis),
                          P(ep_axis, fsdp_ax)]
    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec,
                  P(ep_axis, fsdp_ax, None), P(ep_axis, fsdp_ax, None),
                  P(ep_axis, None, fsdp_ax), *mlp_specs),
        out_specs=(tok_spec, P()), check_vma=False,
    )(xf, p["wi"], p["wg"], p["wo"], *mlp_args)
    return y.reshape(b, s, d).astype(x.dtype), aux


def _routing_local(router, xf_l, cfg: ModelConfig):
    """Per-shard routing; returns (gate, idx, (me_sum, ce_cnt, n_tokens))
    for the cross-shard aux reduction."""
    E, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xf_l.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    me_sum = probs.sum(0)
    ce_cnt = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    return gate, idx, (me_sum, ce_cnt, jnp.float32(xf_l.shape[0]))
