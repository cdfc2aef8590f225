"""SPMD tests on a small multi-device host mesh (subprocess-isolated so
the main test process keeps its single-device view)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestShardedTrainStep:
    def test_train_step_matches_single_device(self):
        out = _run("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.configs import get_config
            from repro.launch.mesh import make_test_mesh
            from repro.launch.sharding import (param_shardings,
                batch_shardings, opt_state_shardings)
            from repro.models.model import (init_params, model_specs,
                input_specs, ShapeCell)
            from repro.models.common import abstract_params
            from repro.train.optimizer import OptConfig
            from repro.train.train_step import (init_train_state,
                make_train_step)

            cfg = get_config("gemma3-1b-smoke")
            params = init_params(cfg, 0)
            opt = init_train_state(cfg, params)
            rng = np.random.default_rng(0)
            B, T = 8, 32
            batch = {
              "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                 (B, T)), jnp.int32),
              "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                 (B, T)), jnp.int32),
              "mask": jnp.ones((B, T), jnp.float32)}
            step = make_train_step(cfg, OptConfig(peak_lr=1e-3))

            # single device reference
            p1, o1, m1 = jax.jit(step)(params, opt, batch)

            # sharded
            mesh = make_test_mesh((4, 2), ("data", "model"))
            specs = model_specs(cfg)
            p_sh = param_shardings(specs, cfg, mesh)
            o_sh = opt_state_shardings(p_sh, mesh)
            cell = ShapeCell("t", T, B, "train")
            b_sh = batch_shardings(cfg, cell, mesh, batch)
            params_s = jax.device_put(params, p_sh)
            opt_s = jax.device_put(opt, o_sh)
            batch_s = jax.device_put(batch, b_sh)
            p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))(
                params_s, opt_s, batch_s)

            print("loss1", float(m1["loss"]), "loss2", float(m2["loss"]))
            assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-5)
            print("SPMD_OK")
        """)
        assert "SPMD_OK" in out

    def test_moe_expert_parallel_matches(self):
        out = _run("""
            import numpy as np, jax, jax.numpy as jnp
            from dataclasses import replace
            from repro.configs import get_config
            from repro.launch.mesh import make_test_mesh
            from repro.launch.sharding import param_shardings, batch_shardings
            from repro.models.model import (init_params, model_specs,
                forward, ShapeCell)

            cfg = replace(get_config("llama4-scout-17b-a16e-smoke"),
                          capacity_factor=8.0)
            params = init_params(cfg, 0)
            rng = np.random.default_rng(0)
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                               jnp.int32)
            ref = forward(params, toks, cfg)

            mesh = make_test_mesh((2, 4), ("data", "model"))
            specs = model_specs(cfg)
            p_sh = param_shardings(specs, cfg, mesh)
            params_s = jax.device_put(params, p_sh)
            fn = jax.jit(lambda p, t: forward(p, t, cfg),
                         in_shardings=(p_sh, None))
            got = fn(params_s, toks)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                       atol=3e-4)
            print("MOE_EP_OK")
        """)
        assert "MOE_EP_OK" in out


class TestShardedRelational:
    def test_row_sharded_query_matches(self):
        out = _run("""
            import numpy as np, jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.mesh import make_test_mesh
            from repro.relational import Session, SessionConfig, expr as E, make_storage
            from repro.relational.datagen import (generate_columns,
                synthetic_schema)

            schema = synthetic_schema(n_int=3, n_dbl=1, n_str=1)
            cols = generate_columns(schema, 4096, seed=0)
            mesh = make_test_mesh((8,), ("data",))
            sharding = NamedSharding(mesh, P("data"))

            plain = Session.from_config(
                SessionConfig.from_legacy_kwargs(budget_bytes=1 << 24))
            st, _ = make_storage("t", schema, 4096, "columnar", cols=cols)
            plain.register(st, columnar_for_stats=cols)
            sharded = Session.from_config(SessionConfig.from_legacy_kwargs(
                budget_bytes=1 << 24, sharding=sharding))
            sharded.register(st, columnar_for_stats=cols)

            q = lambda s: [
              s.table("t").filter(E.cmp("n1", ">", 300)).project("n1","n2"),
              s.table("t").filter(E.cmp("n2", ">", 1000)).project("n2"),
            ]
            r1 = plain.run_batch(q(plain), mqo=True)
            r2 = sharded.run_batch(q(sharded), mqo=True)
            for a, b in zip(r1.results, r2.results):
                assert a.table.row_multiset() == b.table.row_multiset()
            print("REL_SPMD_OK")
        """)
        assert "REL_SPMD_OK" in out


class TestElasticRestore:
    def test_save_on_4_restore_on_2(self, tmp_path):
        save_code = f"""
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.ckpt.checkpoint import CheckpointManager
            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh((4,), ("data",))
            sh = NamedSharding(mesh, P("data"))
            tree = {{"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                                         sh)}}
            mgr = CheckpointManager(r"{tmp_path}")
            mgr.save(1, tree, blocking=True)
            print("SAVED")
        """
        assert "SAVED" in _run(save_code, devices=4)
        restore_code = f"""
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.ckpt.checkpoint import CheckpointManager
            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh((2,), ("data",))
            sh = {{"w": NamedSharding(mesh, P("data"))}}
            mgr = CheckpointManager(r"{tmp_path}")
            step, tree = mgr.restore({{"w": jnp.zeros((8, 8))}},
                                     shardings=sh)
            assert step == 1
            np.testing.assert_array_equal(
                np.asarray(tree["w"]), np.arange(64.0).reshape(8, 8))
            assert len(tree["w"].sharding.device_set) == 2
            print("ELASTIC_OK")
        """
        assert "ELASTIC_OK" in _run(restore_code, devices=2)


class TestGradCompression:
    def test_bf16_allreduce_in_lowered_program(self):
        """The compressed step emits a bf16 cross-data all-reduce (half
        the ICI bytes).  Asserted on the pre-optimization lowering: the
        CPU backend's algebraic simplifier hoists the convert above the
        reduce, while the TPU backend keeps bf16 reductions — so the
        post-optimization check is only meaningful on TPU."""
        out = _run("""
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            from repro.launch.mesh import make_test_mesh

            mesh = make_test_mesh((4,), ("data",))
            W = jnp.zeros((256, 256))
            X = jnp.zeros((32, 256))

            def loss(w, x):
                return jnp.sum(jnp.tanh(x @ w) ** 2)

            def step_f32(w, x):
                g = jax.grad(loss)(w, x)
                return jax.lax.pmean(g, "data")

            def step_bf16(w, x):
                g = jax.grad(loss)(w, x)
                g16 = g.astype(jnp.bfloat16)
                return jax.lax.pmean(g16, "data").astype(jnp.float32)

            def lower(step):
                f = shard_map(step, mesh=mesh,
                              in_specs=(P(), P("data", None)),
                              out_specs=P(), check_vma=False)
                return jax.jit(f).lower(W, X).as_text()

            def ar_dtypes(txt):
                # StableHLO all_reduce result type follows on later
                # lines: inspect the 600 chars after each occurrence
                out = []
                for chunk in txt.split('stablehlo.all_reduce')[1:]:
                    window = chunk[:600]
                    if 'bf16>' in window:
                        out.append('bf16')
                    elif 'f32>' in window:
                        out.append('f32')
                return out

            assert "f32" in ar_dtypes(lower(step_f32))
            assert "bf16" in ar_dtypes(lower(step_bf16))
            # numerics: compressed result within bf16 quantization
            f = jax.jit(shard_map(step_f32, mesh=mesh,
                                  in_specs=(P(), P("data", None)),
                                  out_specs=P(), check_vma=False))
            c = jax.jit(shard_map(step_bf16, mesh=mesh,
                                  in_specs=(P(), P("data", None)),
                                  out_specs=P(), check_vma=False))
            rng = np.random.default_rng(0)
            w = jnp.asarray(rng.standard_normal((256, 256)) * 0.05,
                            jnp.float32)
            x = jnp.asarray(rng.standard_normal((32, 256)), jnp.float32)
            np.testing.assert_allclose(np.asarray(f(w, x)),
                                       np.asarray(c(w, x)),
                                       atol=1e-2, rtol=2e-2)
            print("GRAD_COMPRESS_OK")
        """, devices=4)
        assert "GRAD_COMPRESS_OK" in out
