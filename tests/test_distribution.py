"""Distribution-layer tests.

Sharding-rule unit tests run in-process on the host device (specs only, no
allocation). Multi-device behaviour (pjit train step on a real 8-device
mesh, dry-run lower+compile on the 512-device production mesh) runs in
subprocesses because XLA_FLAGS must be set before jax initialises.
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # pin the CPU platform: without it jax probes the TPU runtime in this
    # container and stalls ~7 minutes per subprocess before falling back.
    # XLA_FLAGS (forced host device count) still applies under cpu.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestShardingRules:
    def _specs(self, arch="tinyllama-1.1b"):
        from repro import configs
        from repro.distributed import sharding
        # spec construction needs only mesh *shape* metadata: a 1x1 mesh
        # on the host device is enough
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        cfg = configs.get(arch)
        return cfg, mesh, sharding

    def test_specs_cover_params(self):
        cfg, mesh, sharding = self._specs()
        import jax as j
        from repro.models import lm
        shapes = j.eval_shape(lambda: lm.init_params(j.random.PRNGKey(0), cfg))
        specs = sharding.param_specs(cfg, mesh, "tp")
        assert (j.tree_util.tree_structure(shapes)
                == j.tree_util.tree_structure(specs))

    def test_fit_spec_drops_nondivisible(self):
        cfg, mesh, sharding = self._specs()
        # mesh is 1x1 here; use a fake larger mesh via shape arithmetic:
        from jax.sharding import Mesh
        import numpy as np
        if jax.device_count() < 2:
            # fit against the 1-device mesh: everything divides
            s = sharding.fit_spec(P("model"), (7,), mesh)
            assert s == P("model")

    def test_dp_axes(self):
        from repro.distributed import sharding
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        assert sharding.dp_axes(mesh) == "data"


class TestMultiDevice:
    """Real 8-device pjit execution (subprocess, forced host devices)."""

    def test_train_step_8dev(self):
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from repro import configs
            from repro.distributed import context as mesh_ctx, sharding
            from repro.launch import steps as steps_lib
            from repro.models import lm

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            cfg = configs.get("qwen3-1.7b", smoke=True)
            mesh_ctx.set_mesh_axes("data", "model")
            with mesh:
                batch = {
                    "inputs": jnp.zeros((4, 32), jnp.int32),
                    "labels": jnp.zeros((4, 32), jnp.int32),
                }
                fn, in_sp, _, opt = steps_lib.build_train_step(
                    cfg, mesh, mode="fsdp_tp", example_batch=batch)
                params = lm.init_params(jax.random.PRNGKey(0), cfg)
                params = jax.device_put(params, sharding.to_shardings(
                    in_sp[0], mesh))
                opt_state = jax.device_put(opt.init(params),
                    sharding.to_shardings(in_sp[1], mesh))
                for _ in range(3):
                    params, opt_state, m = fn(params, opt_state, batch)
                print("LOSS", float(m["loss"]))
            """)
        loss = float(out.strip().split("LOSS")[-1])
        assert 0.0 < loss < 20.0

    def test_constrain_binds_held_mesh(self):
        """A mesh installed with the axes (as a spec-built service or a
        forced test mesh does) resolves `constrain` outside `with mesh:`."""
        out = run_sub("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            from repro.distributed import context as mesh_ctx
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((2, 2), ("data", "model"))
            mesh_ctx.set_mesh_axes("data", "model", mesh)
            f = jax.jit(lambda x: mesh_ctx.constrain(x * 2, "dp", None,
                                                     "model", None))
            y = f(jnp.ones((4, 3, 4, 8)))
            assert float(y.sum()) == 2 * 4 * 3 * 4 * 8
            print("SPEC", y.sharding.spec)
            """)
        assert "SPEC PartitionSpec('data', None, 'model')" in out

    def test_elastic_remesh_8dev(self):
        """Checkpoint on a (4,2) mesh restores onto (2,4) and keeps training."""
        out = run_sub("""
            import os, tempfile
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from repro import configs
            from repro.checkpoint.checkpointer import Checkpointer
            from repro.distributed import context as mesh_ctx, sharding
            from repro.ft import elastic
            from repro.launch import steps as steps_lib
            from repro.models import lm

            cfg = configs.get("tinyllama-1.1b", smoke=True)
            batch = {"inputs": jnp.zeros((4, 16), jnp.int32),
                     "labels": jnp.ones((4, 16), jnp.int32)}
            ckdir = tempfile.mkdtemp()

            from repro.launch.mesh import make_mesh
            mesh_a = make_mesh((4, 2), ("data", "model"))
            mesh_ctx.set_mesh_axes("data", "model")
            with mesh_a:
                fn, in_sp, _, opt = steps_lib.build_train_step(
                    cfg, mesh_a, example_batch=batch)
                params = jax.device_put(
                    lm.init_params(jax.random.PRNGKey(0), cfg),
                    sharding.to_shardings(in_sp[0], mesh_a))
                opt_state = jax.device_put(opt.init(params),
                    sharding.to_shardings(in_sp[1], mesh_a))
                params, opt_state, m0 = fn(params, opt_state, batch)
                ck = Checkpointer(ckdir)
                ck.save(0, {"p": params, "o": opt_state})

            from repro.launch.mesh import make_mesh
            mesh_b = make_mesh((2, 4), ("data", "model"))
            with mesh_b:
                fn2, in_sp2, _, opt2 = steps_lib.build_train_step(
                    cfg, mesh_b, example_batch=batch)
                like = {"p": params, "o": opt_state}
                state = elastic.remesh_restore(
                    ck, 0, like, mesh_b,
                    {"p": in_sp2[0], "o": in_sp2[1]})
                p2, o2, m1 = fn2(state["p"], state["o"], batch)
                print("LOSSES", float(m0["loss"]), float(m1["loss"]))
            """)
        l0, l1 = map(float, out.strip().split("LOSSES")[-1].split())
        assert l1 < l0 + 1.0  # continued training, no blow-up

    @pytest.mark.slow
    def test_production_dryrun_one_cell(self):
        """512-device multi-pod lower+compile for one cell end-to-end."""
        out = run_sub("""
            import sys
            sys.argv = ["dryrun", "--arch", "qwen3-1.7b", "--shape",
                        "train_4k", "--mesh", "multi", "--out",
                        "/tmp/dryrun_test"]
            from repro.launch import dryrun
            dryrun.main()
            """, timeout=900)
        assert "all dry-run cells passed" in out


class TestCompileCache:
    """`repro.launch.compile_cache`: the environment wins, else a fixed
    directory inside the checkout that git ignores."""

    def test_env_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch import compile_cache
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_in_checkout(self, monkeypatch):
        from repro.launch import compile_cache
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        before = jax.config.jax_compilation_cache_dir
        try:
            path = compile_cache.use_compile_cache()
            assert path == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
