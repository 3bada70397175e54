import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""EXPERIMENTS §Perf cell 3: PCA-compressed cross-pod gradient exchange.

The paper's Jacobi/SVD engine applied as a distributed-optimization trick:
on the 2x16x16 multi-pod mesh, the "pod" axis is the slow link.  The whole
step runs in a fully-manual shard_map (data-parallel over all 512 devices
for this experiment); gradients are psum'd over the fast in-pod axes
("data","model"), then the pod exchange is either

  baseline   -- lax.pmean of every gradient leaf over "pod"
  compressed -- PowerSGD-style rank-r exchange: pmean of P (m,r) and
                Q (n,r) factors only, orthonormalised via the MANOJAVAM
                Jacobi engine; error feedback kept pod-local.

Both variants lower+compile on the production multi-pod mesh.  The in-pod
collectives are identical across variants, so the difference in HLO
collective bytes is exactly the pod-exchange saving.

  PYTHONPATH=src python -m repro.launch.pod_compression \
      --arch granite-8b --layers 4 --rank 8
"""
import argparse
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.optim import compression as comp
from repro.parallel.sharding import REPLICATED


def build(cfg, mesh, seq, global_batch, mode: str, rank: int):
    opt_cfg = adamw.AdamWConfig()
    comp_cfg = comp.CompressionConfig(rank=rank, axis_name="pod",
                                      min_size=65536)
    abstract_params = tfm.param_values(tfm.abstract_init(cfg))
    n_pods = mesh.shape["pod"]
    inpod = ("data", "model")

    def loss_of(p, batch):
        return tfm.loss_fn(p, batch, cfg, REPLICATED)[0]

    def device_local(params, tokens, comp_state):
        grads = jax.grad(loss_of)(params, {"tokens": tokens})
        # fast in-pod reduction (identical in both variants)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, inpod), grads)
        if mode == "compressed":
            state = jax.tree.map(lambda l: l[0], comp_state)
            grads, new_state, _ = comp.compress_tree(grads, state, comp_cfg)
            new_state = jax.tree.map(lambda l: l[None], new_state)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "pod"), grads)
            new_state = comp_state
        opt = adamw.init(params, opt_cfg)
        new_p, _, _ = adamw.update(grads, opt, params, opt_cfg)
        return new_p, new_state

    ab_comp = jax.eval_shape(
        lambda p: comp.init_state(p, comp_cfg, jax.random.PRNGKey(0)),
        abstract_params)
    ab_comp = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_pods,) + l.shape, l.dtype),
        ab_comp)
    tokens = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)

    rep = lambda l: P(*([None] * getattr(l, "ndim", 0)))
    params_spec = jax.tree.map(rep, abstract_params)
    tok_spec = P(("pod", "data", "model"), None)
    comp_spec = jax.tree.map(lambda l: P("pod", *([None] * (l.ndim - 1))),
                             ab_comp)

    fn = jax.shard_map(device_local, mesh=mesh,
                       in_specs=(params_spec, tok_spec, comp_spec),
                       out_specs=(params_spec, comp_spec), check_vma=False)
    in_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         (params_spec, tok_spec, comp_spec),
                         is_leaf=lambda x: isinstance(x, P))
    return fn, in_sh, (abstract_params, tokens, ab_comp)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    cfg = dataclasses.replace(get_config(args.arch), n_layers=args.layers,
                              remat=False)
    mesh = make_production_mesh(multi_pod=True)
    rec = {"arch": args.arch, "layers": args.layers, "rank": args.rank,
           "seq": args.seq, "batch": args.batch}
    for mode in ("baseline", "compressed"):
        fn, in_sh, ab = build(cfg, mesh, args.seq, args.batch, mode,
                              args.rank)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh).lower(*ab).compile()
        colls = collective_bytes(compiled.as_text())
        rec[mode] = {"collectives": colls,
                     "total_bytes": float(sum(colls.values()))}
        print(f"{mode}: { {k: f'{v:.3e}' for k, v in colls.items()} } "
              f"total={rec[mode]['total_bytes']:.3e}", flush=True)
    b = rec["baseline"]["total_bytes"]
    c = rec["compressed"]["total_bytes"]
    rec["pod_exchange_savings_bytes"] = b - c
    rec["reduction_factor_total"] = b / max(c, 1)
    print(f"pod-exchange saving: {b - c:.3e} bytes/dev "
          f"({b / max(c, 1):.2f}x total-collective reduction)", flush=True)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"pod_compression_{args.arch}_L{args.layers}_r{args.rank}.json"
     ).write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
