"""Multi-device SPMD tests, run in subprocesses with
--xla_force_host_platform_device_count=8 (shared harness in tests/_mesh.py)
so they see a real 8-way mesh no matter how the main pytest process was
launched."""
import pytest

from _mesh import run_in_mesh_subprocess as _run


def test_distributed_covariance_matches_local():
    out = _run("""
        from repro.core import covariance, distributed_covariance
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((256, 24)), jnp.float32)
        c_dist = distributed_covariance(x, mesh, block_m=16)
        c_ref = covariance(x)
        err = float(jnp.max(jnp.abs(c_dist - c_ref)))
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 1e-3


def test_distributed_pca_matches_numpy():
    out = _run("""
        from repro.core import PCAConfig, fit_distributed
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(1)
        x = (rng.standard_normal((256, 4)) @
             rng.standard_normal((4, 12))).astype(np.float32)
        res = fit_distributed(jnp.asarray(x), mesh,
                              PCAConfig(T=32, sweeps=15))
        from repro.core import standardize, covariance
        xs, _, _ = standardize(jnp.asarray(x))
        ref = np.linalg.eigh(np.asarray(covariance(xs)))[0][::-1]
        err = float(np.max(np.abs(np.asarray(res.eigenvalues) - ref)))
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 1e-2


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """2x4 mesh (DP x TP with FSDP) vs single-device: one train step on a
    reduced dense model must agree.  Slow tier: two full train-step
    compiles in a subprocess; the fast tier keeps the sharded-forward
    coverage via the MoE/ring/decode tests."""
    out = _run("""
        import dataclasses
        from repro.configs import reduced_config
        from repro.configs.shapes import ShapeCell
        from repro.launch import steps as steps_mod
        from repro.models import transformer as tfm
        from repro.optim import adamw
        from repro.parallel.sharding import REPLICATED

        cfg = dataclasses.replace(reduced_config("granite-8b"), tp=4,
                                  n_layers=2)
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeCell("t", 32, 4, "train")
        step, in_sh, out_sh, _, rules = steps_mod.build_train_step(
            cfg, mesh, shape)
        params = tfm.param_values(tfm.init_model(jax.random.PRNGKey(0), cfg))
        opt_cfg = adamw.AdamWConfig()
        state = steps_mod.TrainState(params, adamw.init(params, opt_cfg),
                                     jnp.int32(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)}
        with mesh:
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            new_state, metrics = jitted(state, batch)
            loss_sharded = float(metrics["loss"])

        # single-device reference
        def loss_fn(p):
            return tfm.loss_fn(p, batch, cfg, REPLICATED)
        (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        newp, _, _ = adamw.update(g, adamw.init(params, opt_cfg), params,
                                  opt_cfg)
        loss_ref = float(l)
        # param update agreement on a sample leaf
        a = np.asarray(jax.device_get(new_state.params["norm_f"]["scale"]))
        b = np.asarray(newp["norm_f"]["scale"])
        print(json.dumps({
            "loss_sharded": loss_sharded, "loss_ref": loss_ref,
            "param_err": float(np.max(np.abs(a - b)))}))
    """)
    assert out["loss_sharded"] == pytest.approx(out["loss_ref"], rel=2e-3)
    assert out["param_err"] < 5e-4


def test_moe_shard_map_matches_single_device():
    out = _run("""
        import dataclasses
        from repro.configs import reduced_config
        from repro.models import moe, transformer as tfm
        from repro.parallel.sharding import REPLICATED, rules_for_mesh

        cfg = dataclasses.replace(reduced_config("arctic-480b"), tp=4,
                                  n_layers=1, n_experts=8,
                                  capacity_factor=4.0)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = rules_for_mesh(mesh)
        key = jax.random.PRNGKey(0)
        p = jax.tree.map(lambda x: x.v if hasattr(x, "v") else x,
                         moe.init_moe(key, cfg),
                         is_leaf=lambda x: hasattr(x, "v"))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 16, cfg.d_model)),
                        jnp.float32)
        with mesh:
            y_sh, aux_sh = jax.jit(
                lambda p, x: moe.apply_moe(p, x, cfg, rules))(p, x)
            y_sh = jax.device_get(y_sh)
        y_ref, aux_ref = jax.jit(
            lambda p, x: moe.apply_moe(p, x, cfg, REPLICATED))(p, x)
        err = float(np.max(np.abs(np.asarray(y_sh) - np.asarray(y_ref))))
        print(json.dumps({"err": err, "aux_sh": float(aux_sh),
                          "aux_ref": float(aux_ref)}))
    """)
    # capacity is applied per data shard in the sharded path, so token drop
    # patterns can differ only when capacity binds; capacity_factor=4 makes
    # it non-binding -> results must match.
    assert out["err"] < 1e-3
    assert out["aux_sh"] == pytest.approx(out["aux_ref"], rel=1e-3)


@pytest.mark.slow
def test_seq_sharded_decode_matches_replicated():
    out = _run("""
        import dataclasses
        from repro.configs import reduced_config
        from repro.models import transformer as tfm
        from repro.parallel.sharding import REPLICATED, Rules

        cfg = dataclasses.replace(reduced_config("granite-8b"), n_layers=2)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = Rules(mesh_axes=("data", "model"), mesh=mesh,
                      seq_over_data=False)
        params = tfm.param_values(tfm.init_model(jax.random.PRNGKey(0), cfg))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 9)),
                             jnp.int32)
        batch = {"tokens": tokens[:, :8]}
        with mesh:
            _, state = jax.jit(lambda p, b: tfm.prefill(
                p, b, cfg, rules, cache_len=16))(params, batch)
            logits, _ = jax.jit(lambda p, s, t: tfm.decode_step(
                p, s, t, cfg, rules))(params, state, tokens[:, 8])
            logits = jax.device_get(logits)
        full = tfm.forward(params, {"tokens": tokens}, cfg, REPLICATED,
                           "train")[0][:, -1, :]
        err = float(np.max(np.abs(np.asarray(logits) -
                                  np.asarray(full))))
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 5e-3


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint written under a (4,2) mesh restores onto (2,2) with
    reshard-on-load (elastic restart)."""
    out = _run(f"""
        import pathlib
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import checkpointer

        d = pathlib.Path({str(tmp_path)!r})
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = NamedSharding(mesh_a, P("data", "model"))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh_a)
        checkpointer.save(d, 3, {{"w": w}}, metadata={{"step": 3}})

        mesh_b = make_mesh((2, 2), ("data", "model"),
                               devices=jax.devices()[:4])
        sh_b = NamedSharding(mesh_b, P("model", "data"))
        restored, meta = checkpointer.restore(
            d, {{"w": jnp.zeros((8, 8))}}, shardings={{"w": sh_b}})
        ok_values = bool(jnp.all(restored["w"] ==
                                 jnp.arange(64.0).reshape(8, 8)))
        ok_sharding = restored["w"].sharding == sh_b
        print(json.dumps({{"ok_values": ok_values,
                           "ok_sharding": bool(ok_sharding),
                           "step": meta["step"]}}))
    """)
    assert out["ok_values"] and out["ok_sharding"] and out["step"] == 3


@pytest.mark.slow
def test_moe_fused_dense_residual_matches_single_device():
    """arctic-style fused (MoE + dense residual in one shard_map psum)
    against the single-device path.  Slow tier: the plain
    test_moe_shard_map_matches_single_device keeps MoE dispatch covered
    fast."""
    out = _run("""
        import dataclasses
        from repro.configs import reduced_config
        from repro.models import moe, transformer as tfm
        from repro.models.layers import init_mlp
        from repro.parallel.sharding import REPLICATED, rules_for_mesh

        cfg = dataclasses.replace(reduced_config("arctic-480b"), tp=4,
                                  n_layers=1, n_experts=8,
                                  capacity_factor=4.0)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = rules_for_mesh(mesh)
        strip = lambda t: jax.tree.map(
            lambda x: x.v if hasattr(x, "v") else x, t,
            is_leaf=lambda x: hasattr(x, "v"))
        p = strip(moe.init_moe(jax.random.PRNGKey(0), cfg))
        p_mlp = strip(init_mlp(jax.random.PRNGKey(1), cfg))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 16, cfg.d_model)),
                        jnp.float32)
        with mesh:
            y_sh, aux_sh = jax.jit(lambda p, m, x: moe.apply_moe(
                p, x, cfg, rules, mlp_res=m))(p, p_mlp, x)
            y_sh = jax.device_get(y_sh)
        y_ref, aux_ref = jax.jit(lambda p, m, x: moe.apply_moe(
            p, x, cfg, REPLICATED, mlp_res=m))(p, p_mlp, x)
        err = float(np.max(np.abs(np.asarray(y_sh) - np.asarray(y_ref))))
        print(json.dumps({"err": err, "aux_sh": float(aux_sh),
                          "aux_ref": float(aux_ref)}))
    """)
    assert out["err"] < 2e-3
    assert out["aux_sh"] == pytest.approx(out["aux_ref"], rel=1e-3)


def test_ring_attention_matches_dense():
    """Sequence-parallel ring attention == dense attention, with a head
    count NOT divisible by the mesh axis (the case TP head-sharding cannot
    handle without padding)."""
    out = _run("""
        from repro.parallel.ring_attention import ring_attention
        from repro.models.attention import _dense_attention
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        B, S, H, D = 4, 64, 6, 16
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        errs = {}
        for causal in (True, False):
            with jax.set_mesh(mesh):
                o = jax.jit(lambda q, k, v: ring_attention(
                    q, k, v, mesh, causal=causal))(q, k, v)
                o = jax.device_get(o)
            ref = _dense_attention(q, k, v, causal, D ** -0.5)
            errs[str(causal)] = float(jnp.max(jnp.abs(o - np.asarray(ref))))
        print(json.dumps(errs))
    """)
    assert out["True"] < 2e-6 and out["False"] < 2e-6


def test_ring_mode_model_matches_chunked():
    """attn_impl='ring' on a 2x4 mesh == chunked single-device model with
    identical weights (qwen reduced: MHA, heads % mesh != 0)."""
    out = _run("""
        import dataclasses
        from repro.configs import reduced_config
        from repro.models import transformer as tfm
        from repro.parallel.sharding import REPLICATED, rules_for_mesh

        cfg_r = dataclasses.replace(reduced_config("qwen1.5-32b"), tp=4,
                                    n_layers=2, attn_impl="ring")
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = rules_for_mesh(mesh)
        params = tfm.param_values(tfm.init_model(jax.random.PRNGKey(0),
                                                 cfg_r))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg_r.vocab_size, (4, 32)), jnp.int32)}
        with jax.set_mesh(mesh):
            lr = jax.device_get(jax.jit(lambda p, b: tfm.forward(
                p, b, cfg_r, rules, "train")[0])(params, batch))
        cfg_c = dataclasses.replace(cfg_r, tp=1, attn_impl="chunked")
        ref = tfm.forward(params, batch, cfg_c, REPLICATED, "train")[0]
        err = float(jnp.max(jnp.abs(lr - np.asarray(ref))))
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 5e-3


def test_ring_attention_gqa_rotates_true_kv():
    """GQA ring: q has 8 heads, KV only 2 -- output must equal dense
    attention with expanded KV."""
    out = _run("""
        from repro.parallel.ring_attention import ring_attention
        from repro.models.attention import _dense_attention
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(1)
        B, S, H, KV, D = 2, 64, 8, 2, 16
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
        with jax.set_mesh(mesh):
            o = jax.jit(lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True))(q, k, v)
            o = jax.device_get(o)
        kx = jnp.repeat(k, H // KV, axis=2)
        vx = jnp.repeat(v, H // KV, axis=2)
        ref = _dense_attention(q, kx, vx, True, D ** -0.5)
        err = float(jnp.max(jnp.abs(o - np.asarray(ref))))
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 2e-6
