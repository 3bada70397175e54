"""Scenario: distributed PCA — the paper's block streaming lifted across a
mesh (covariance accumulated shard-wise with a single psum), plus the
TPU-native parallel-Jacobi schedule and the analytical fabric model.

    PYTHONPATH=src python examples/pca_pipeline.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import PCAConfig, fit_distributed
from repro.core.memory_model import ARTIX7, VIRTEX_US, pca_seconds
from repro.parallel.sharding import make_mesh

mesh = make_mesh((len(jax.devices()),), ("data",))
rng = np.random.default_rng(1)
X = (rng.standard_normal((4096, 8)) @ rng.standard_normal((8, 64))
     ).astype(np.float32)

res = fit_distributed(jnp.asarray(X), mesh,
                      PCAConfig(T=128, S=8, pivot="parallel", sweeps=15))
print(f"devices: {len(jax.devices())}  eigenvalues[:5]:",
      np.round(np.asarray(res.eigenvalues[:5]), 1))
print(f"rel off-diag after 15 sweeps: {float(res.off_norm):.2e}")

print("\nfabric-model latency for this dataset (paper Sec. VII-A):")
for name, cfgf in (("MANOJAVAM(4,8)@Artix-7", ARTIX7),
                   ("MANOJAVAM(16,32)@Virtex-US+", VIRTEX_US)):
    est = pca_seconds(*X.shape, cfgf)
    print(f"  {name:28s} total={est['total_s']*1e3:8.2f} ms "
          f"energy={est['energy_j']*1e3:8.2f} mJ")
