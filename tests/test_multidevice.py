"""Multi-device shard_map correctness: run subprocesses with 8 host devices
(XLA_FLAGS must be set before jax import, hence subprocess isolation)."""
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code, n_devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=420)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_decode_attention_sharded_matches_oracle():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.distributed.decode_attention import decode_attention
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        B, S, HQ, HKV, D = 4, 64, 8, 4, 32
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, 1, HQ, D), jnp.float32)
        # the cache layout (B, Hkv, D, S): the sequence is the last axis
        ck = jax.random.normal(ks[1], (B, HKV, D, S), jnp.float32)
        cv = jax.random.normal(ks[2], (B, HKV, D, S), jnp.float32)
        pos = jnp.asarray(40, jnp.int32)
        with mesh:
            out = jax.jit(lambda q, k, v: decode_attention(
                q, k, v, pos, mesh))(q, ck, cv)
        ref = decode_attention(q, ck, cv, pos, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # window + softcap variants
        with mesh:
            out = jax.jit(lambda q, k, v: decode_attention(
                q, k, v, pos, mesh, window=16, logit_cap=30.0))(q, ck, cv)
        ref = decode_attention(q, ck, cv, pos, None, window=16,
                               logit_cap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        print("decode_attention sharded OK")
    """)


def test_decode_step_seq_sharded_cache_matches_single_device():
    """Masked decode at per-slot positions with the stacked slot cache
    batch-sharded on "data" and seq-sharded on "model" (its last axis):
    the same logits as one device."""
    run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_arch, reduced
        from repro.distributed.sharding import make_plan
        from repro.models import Model
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        plan = make_plan(mesh, 0)
        B, S = 4, 32
        for arch in ("llama3.2-3b", "gemma2-27b"):
            cfg = dataclasses.replace(reduced(get_arch(arch)),
                                      compute_dtype="float32")
            params = Model(cfg).init(jax.random.key(0))
            toks = jax.random.randint(jax.random.key(1), (B, 6), 1,
                                      cfg.vocab_size)
            out = []
            for m in (Model(cfg), Model(cfg)):
                cache = m.init_cache(B, S)
                if out:
                    m.mesh = mesh
                    cache = jax.device_put(
                        cache, plan.cache_shardings(m.cache_specs(B, S), B))
                    assert cache["pos0"]["kv"]["k"].sharding.spec[4] \
                        == "model"
                step = jax.jit(m.decode_step)
                for t in range(6):
                    cur = jnp.arange(B, dtype=jnp.int32) + t
                    with mesh:
                        lg, cache = step(params, {
                            "tokens": toks[:, t:t + 1],
                            "positions": cur[:, None]}, cache, cur)
                out.append(np.asarray(lg))
            np.testing.assert_allclose(out[1], out[0], rtol=1e-5, atol=1e-5)
        print("seq-sharded decode OK")
    """)


def test_moe_shard_map_matches_local():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs.base import ArchConfig, MoEConfig
        from repro.models.moe import moe_apply, moe_specs
        from repro.models.layers import init_params
        cfg = ArchConfig(
            name="t", family="moe", num_layers=2, d_model=16, num_heads=4,
            num_kv_heads=2, d_ff=32, vocab_size=64, head_dim=8,
            moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=32,
                          capacity_factor=8.0))
        p = init_params(moe_specs(cfg), jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 16, 16), jnp.float32)
        y_local, aux_local = moe_apply(p, cfg, x, mesh=None)
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        with mesh:
            y_sh, aux_sh = jax.jit(
                lambda p, x: moe_apply(p, cfg, x, mesh=mesh))(p, x)
        # sharded dispatch routes per-DP-shard: same result when capacity
        # is non-binding
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_local),
                                   rtol=3e-3, atol=3e-3)
        print("moe shard_map OK")
    """)


def test_moe_serve_shard_map_matches_local():
    """The serving MoE over an expert-parallel mesh (4 shards of 8
    experts, the batch over 2): each shard's grouped products over its
    own experts, summed, equal the one-device layer, and the loads
    concatenate to the one-device loads."""
    run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_arch, reduced
        from repro.models.moe import moe_serve, moe_specs
        from repro.models.layers import init_params
        base = reduced(get_arch("moonlight-16b-a3b"))
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, num_experts=8, top_k=3))
        p = init_params(moe_specs(cfg), jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model),
                              jnp.float32)
        y_local, load_local = moe_serve(p, cfg, x, mesh=None)
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        with mesh:
            y_sh, load_sh = jax.jit(
                lambda p, x: moe_serve(p, cfg, x, mesh=mesh))(p, x)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_local),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(load_sh),
                                      np.asarray(load_local))
        print("moe serve shard_map OK")
    """)


def test_train_step_sharded_matches_single_device():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.models import Model
        from repro.train.loop import make_train_step
        from repro.train.optimizer import optimizer_for, schedule_for
        from repro.distributed.sharding import ShardingPlan
        cfg = reduced(get_arch("llama3.2-3b"))
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        opt = optimizer_for(cfg)
        lr = schedule_for(cfg.name, 1e-3, 100)
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        step0 = jnp.asarray(0, jnp.int32)
        # single device
        sf = make_train_step(model, opt, lr)
        p1, o1, m1 = jax.jit(sf)(params, opt.init(params), batch, step0)
        # 2x4 mesh with the production sharding plan
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        model2 = Model(cfg)
        model2.mesh = mesh
        plan = ShardingPlan(mesh=mesh, fsdp=True, dp_axes=("data",))
        psh = plan.param_shardings(model2.param_logical_axes(),
                                   model2.param_structs())
        sf2 = make_train_step(model2, opt, lr)
        with mesh:
            params_sh = jax.device_put(params, psh)
            p2, o2, m2 = jax.jit(sf2)(params_sh, opt.init(params_sh),
                                      batch, step0)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2, \
            (float(m1["loss"]), float(m2["loss"]))
        # updated params agree across the mesh
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=3e-2, atol=3e-2)
        print("sharded train step OK, loss", float(m2["loss"]))
    """)


def test_elastic_restore_across_mesh_shapes():
    """Save a sharded train state on a 2x4 mesh, restore it onto a 4x2
    mesh with different shardings and keep training — the elastic-rescale
    path (node loss -> re-mesh -> resume)."""
    run_py("""
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import Mesh
        from repro.configs import get_arch, reduced
        from repro.models import Model
        from repro.train.loop import make_train_step
        from repro.train.optimizer import optimizer_for, schedule_for
        from repro.train.checkpoint import save_checkpoint, \
            restore_checkpoint
        from repro.distributed.sharding import ShardingPlan

        cfg = reduced(get_arch("llama3.2-3b"))
        opt = optimizer_for(cfg)
        lr = schedule_for(cfg.name, 1e-3, 100)
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        ckpt = tempfile.mkdtemp()

        def setup(shape):
            mesh = Mesh(np.asarray(jax.devices()).reshape(*shape),
                        ("data", "model"))
            model = Model(cfg)
            model.mesh = mesh
            plan = ShardingPlan(mesh=mesh, fsdp=True, dp_axes=("data",))
            psh = plan.param_shardings(model.param_logical_axes(),
                                       model.param_structs())
            return mesh, model, plan, psh

        # train 2 steps on mesh A, checkpoint
        mesh, model, plan, psh = setup((2, 4))
        params = jax.device_put(model.init(jax.random.key(0)), psh)
        state = opt.init(params)
        sf = jax.jit(make_train_step(model, opt, lr))
        with mesh:
            for s in range(2):
                params, state, m = sf(params, state, batch,
                                      jnp.asarray(s, jnp.int32))
        save_checkpoint(ckpt, 2, (params, state))
        loss_a = float(m["loss"])

        # restore onto mesh B (different shape => different shardings)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, model, plan, psh = setup((4, 2))
        p0 = model.init(jax.random.key(0))
        osh = {"m": psh, "v": psh,
               "count": NamedSharding(mesh, P())}   # adamw slots
        (params2, state2), step, _ = restore_checkpoint(
            ckpt, (p0, opt.init(p0)), shardings=(psh, osh))
        assert step == 2
        sf = jax.jit(make_train_step(model, opt, lr))
        with mesh:
            params2, state2, m2 = sf(params2, state2, batch,
                                     jnp.asarray(2, jnp.int32))
        assert np.isfinite(float(m2["loss"]))
        print("elastic restore OK: mesh A loss", loss_a,
              "-> mesh B step-3 loss", float(m2["loss"]))
    """)


def test_fleet_sharded_matches_host_oracle():
    """The packed fleet axis auto-shards over an 8-device CPU mesh
    (reconstruction AND streamed attribution) and stays ≤1e-5 of the
    float64 host oracle / identical to the unsharded path."""
    run_py("""
        import numpy as np, jax
        assert jax.device_count() == 8
        from repro.distributed.sharding import fleet_mesh
        from repro.fleet import (FleetStream, fleet_reconstruct,
                                 fleet_reconstruct_host, pack_traces)
        from repro.core.measurement_model import SensorSpec
        from repro.core.sensors import SensorTrace

        rng = np.random.default_rng(0)
        traces = []
        for i in range(16):
            k = 300 - int(rng.integers(0, 40))
            dt = rng.uniform(0.5e-3, 2e-3, k)
            t = np.cumsum(dt); p = rng.uniform(40, 260, k)
            e = np.cumsum(p * dt)
            wb = 24 if i % 2 == 0 else 0
            spec = SensorSpec(name=f"s{i}", scope="chip",
                              kind="energy_cum", quantum=1e-6,
                              wrap_bits=wb)
            if wb:
                e = np.mod(e, (2.0 ** wb) * spec.quantum)
            traces.append(SensorTrace(spec.name, spec, t + 1e-4, t, e))

        packed = pack_traces(traces)
        mesh = fleet_mesh()
        assert mesh is not None and mesh.shape["fleet"] == 8
        power, times, valid = fleet_reconstruct(packed)  # auto-sharded
        p1, _, v1 = fleet_reconstruct(packed, mesh=None)
        ph, th, vh = fleet_reconstruct_host(packed)
        pj, vj = np.asarray(power), np.asarray(valid)
        assert (vj == vh).all() and (vj == np.asarray(v1)).all()
        rel = (np.abs(pj[vj] - ph[vh])
               / np.maximum(np.abs(ph[vh]), 1.0)).max()
        assert rel <= 1e-5, rel
        np.testing.assert_allclose(pj, np.asarray(p1), rtol=1e-6,
                                   atol=1e-5)

        span = float(max(tr.t_measured[-1] for tr in traces))
        edges = np.linspace(0.0, span, 5)
        wins = list(zip(edges[:-1], edges[1:]))
        s_sh = FleetStream(wins, packed.shape[0],
                           wrap_period=packed.wrap_period)   # auto mesh
        s_un = FleetStream(wins, packed.shape[0],
                           wrap_period=packed.wrap_period, mesh=None)
        assert s_sh.mesh is not None
        for lo in range(0, packed.shape[1], 100):
            s_sh.update(packed.times[:, lo:lo + 100],
                        packed.energy[:, lo:lo + 100])
            s_un.update(packed.times[:, lo:lo + 100],
                        packed.energy[:, lo:lo + 100])
        np.testing.assert_allclose(s_sh.totals(), s_un.totals(),
                                   rtol=1e-6, atol=1e-4)
        print("fleet sharding OK")
    """)


def test_fleet_nondivisible_rows_pad_and_stay_sharded():
    """Fleet sizes that do NOT divide the mesh (rows = mesh±1 and the
    8-row pack tile on a 3-device mesh) must pad masked rows up to
    divisibility and KEEP the sharded path — the old fallback silently
    dropped to unsharded execution.  Padded results must equal the
    unsharded path / the float64 host oracle."""
    run_py("""
        import numpy as np, jax
        assert jax.device_count() == 3
        from repro.distributed.sharding import (fleet_mesh,
                                                fleet_row_padding,
                                                fleet_rows_divisible)
        from repro.fleet import (FleetStream, fleet_reconstruct,
                                 fleet_reconstruct_host, pack_traces)
        from repro.core.measurement_model import SensorSpec
        from repro.core.sensors import SensorTrace

        mesh = fleet_mesh()
        assert mesh is not None and mesh.shape["fleet"] == 3
        assert not fleet_rows_divisible(mesh, 8)
        assert fleet_row_padding(mesh, 8) == 1
        assert fleet_row_padding(mesh, 16) == 2

        def make_traces(n):
            rng = np.random.default_rng(5)
            out = []
            for i in range(n):
                k = 260 - int(rng.integers(0, 30))
                dt = rng.uniform(0.5e-3, 2e-3, k)
                t = np.cumsum(dt); p = rng.uniform(40, 260, k)
                e = np.cumsum(p * dt)
                wb = 24 if i % 2 == 0 else 0
                spec = SensorSpec(name=f"s{i}", scope="chip",
                                  kind="energy_cum", quantum=1e-6,
                                  wrap_bits=wb)
                if wb:
                    e = np.mod(e, (2.0 ** wb) * spec.quantum)
                out.append(SensorTrace(spec.name, spec, t + 1e-4, t, e))
            return out

        # reconstruction: 6 traces -> F=8 rows, 3-device mesh -> pad 9
        packed = pack_traces(make_traces(6))
        assert packed.shape[0] == 8
        power, times, valid = fleet_reconstruct(packed)   # auto mesh
        p_un, _, v_un = fleet_reconstruct(packed, mesh=None)
        ph, th, vh = fleet_reconstruct_host(packed)
        pj, vj = np.asarray(power), np.asarray(valid)
        assert pj.shape[0] == 8                  # padding sliced off
        assert (vj == vh).all() and (vj == np.asarray(v_un)).all()
        rel = (np.abs(pj[vj] - ph[vh])
               / np.maximum(np.abs(ph[vh]), 1.0)).max()
        assert rel <= 1e-5, rel
        np.testing.assert_allclose(pj, np.asarray(p_un), rtol=1e-6,
                                   atol=1e-5)

        # streamed attribution at rows = mesh - 1 and mesh + 1
        rng = np.random.default_rng(11)
        for n_rows in (2, 4):
            dt = rng.uniform(0.5e-3, 2e-3, (n_rows, 300))
            t = np.cumsum(dt, axis=1).astype(np.float32)
            p = rng.uniform(40, 260, (n_rows, 300))
            e = np.cumsum(p * dt, axis=1).astype(np.float32)
            span = float(t.max())
            edges = np.linspace(0.0, span, 4)
            wins = list(zip(edges[:-1], edges[1:]))
            s_sh = FleetStream(wins, n_rows)             # auto mesh
            s_un = FleetStream(wins, n_rows, mesh=None)
            assert s_sh.mesh is not None, n_rows
            assert s_sh._attr._row_pad == (-n_rows) % 3, n_rows
            for lo in range(0, 300, 100):
                s_sh.update(t[:, lo:lo + 100], e[:, lo:lo + 100])
                s_un.update(t[:, lo:lo + 100], e[:, lo:lo + 100])
            assert s_sh.totals().shape == (n_rows, 3)
            np.testing.assert_allclose(s_sh.totals(), s_un.totals(),
                                       rtol=1e-6, atol=1e-4)
        print("nondivisible fleet padding OK")
    """, n_devices=3)


def test_dryrun_single_cell_tiny_mesh():
    """The dry-run machinery itself (lower+compile+costs) on a 2x4 mesh."""
    run_py("""
        import numpy as np, jax
        devices = jax.devices()
        assert len(devices) == 8
        import repro.launch.mesh as mesh_mod
        from jax.sharding import Mesh
        # shrink the production mesh for the 8-device test process
        mesh_mod.make_production_mesh = lambda multi_pod=False: Mesh(
            np.asarray(devices).reshape(2, 4), ("data", "model"))
        import repro.launch.dryrun as dr
        dr.make_production_mesh = mesh_mod.make_production_mesh
        rec, compiled = dr.lower_cell("whisper-base", "train_4k")
        assert rec["status"] == "ok", rec
        assert rec["hlo_flops_per_device"] > 0
        assert rec["roofline"]["compute_s"] > 0
        print("dryrun cell OK:", rec["bottleneck"])
    """)
