"""Ring attention: sequence-parallel exact attention via collective_permute.

Q/K/V live sharded on the SEQUENCE dim over a mesh axis; each shard holds
its query block stationary while KV blocks rotate around the ring
(`lax.ppermute`), folding each visiting block into an online softmax --
flash attention's accumulation across devices.  Exact for causal and
non-causal attention at ANY head count (no TP head padding), with
communication = (ring_size - 1) x local *true-KV* bytes per layer
(GQA K/V rotates unexpanded: G x fewer ppermute bytes than rotating
query-head-expanded KV), overlappable with the per-step attention compute.

This is the SP alternative to Megatron head-TP for long-context prefill
(DESIGN.md "Parallelism design"); validated against dense attention in
tests/test_distributed.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -1e30


def _make_local(axis: str, n_static: int, causal: bool, scale: float,
                unroll: bool = False):
    perm = [(j, (j + 1) % n_static) for j in range(n_static)]

    def local(q, k, v):
        """q: (B, S_l, H, D); k/v: (B, S_l, KV, D) TRUE GQA heads -- only
        the true KV rotates; the group expansion happens implicitly in the
        grouped einsums."""
        idx = lax.axis_index(axis)
        b, s_l, h, d = q.shape
        kv = k.shape[2]
        g = h // kv
        qf = q.reshape(b, s_l, kv, g, d).astype(jnp.float32)
        q_pos = idx * s_l + jnp.arange(s_l)

        def step(i, carry):
            k_cur, v_cur, m, l, acc = carry
            src = (idx - i) % n_static
            k_pos = src * s_l + jnp.arange(s_l)
            s_ = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                            k_cur.astype(jnp.float32)) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s_ = jnp.where(mask[None, None, None], s_, _NEG)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, v_cur.astype(jnp.float32))
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return k_nxt, v_nxt, m_new, l_new, acc_new

        m0 = jnp.full((b, kv, g, s_l), _NEG, jnp.float32)
        l0 = jnp.zeros((b, kv, g, s_l), jnp.float32)
        a0 = jnp.zeros((b, kv, g, s_l, d), jnp.float32)
        carry = (k, v, m0, l0, a0)
        if unroll:  # dry-run cost extraction: no while loops in HLO
            for i in range(n_static):
                carry = step(i, carry)
            _, _, m, l, acc = carry
        else:
            _, _, m, l, acc = lax.fori_loop(0, n_static, step, carry)
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, KV, G, S_l, D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, s_l, h, d)
        return out.astype(q.dtype)

    return local


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "model",
                   batch_axes=("data",), causal: bool = True,
                   scale: Optional[float] = None, unroll: bool = False):
    """q: (B, S, H, D); k/v: (B, S, KV, D) with H % KV == 0 (GQA groups).
    S sharded over ``seq_axis``, B over ``batch_axes``.  Returns
    (B, S, H, D) with the same sharding."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[seq_axis]
    local = _make_local(seq_axis, n, causal, scale, unroll=unroll)
    spec = P(tuple(a for a in batch_axes if a), seq_axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
