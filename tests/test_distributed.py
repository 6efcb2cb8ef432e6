"""Distributed search + sharding policy.

The multi-device tests run in a SUBPROCESS with
--xla_force_host_platform_device_count=8 so the rest of the suite keeps
seeing the real (single) device, per the dry-run isolation rule.  The child
is pinned to the CPU backend: its 8 devices are virtual CPU devices, never
an attached accelerator.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(data=4, model=2)
""")

_SEARCH_PROG = _CHILD_PRELUDE + textwrap.dedent("""
    from repro.distributed.search import (make_flat_search, make_pq_search,
                                          make_hamming_search)
    from repro.core import exact_knn
    from repro.core.pq import ProductQuantizer, PQConfig, build_adc_lut
    from repro.core.bq import BinaryQuantizer, BQConfig
    from repro.core.distances import normalize
    from repro.data.synthetic import gaussian_mixture

    N, D, Q, K = 1600, 32, 8, 10
    x = gaussian_mixture(N, D, seed=0)
    q = gaussian_mixture(Q, D, seed=1)

    # ---- flat: sharded == exact ----
    xn = np.asarray(normalize(jnp.asarray(x)))
    qn = np.asarray(normalize(jnp.asarray(q)))
    fn = make_flat_search(mesh, k=K, metric="cosine", dim=D)
    d, ids = fn(jnp.asarray(xn), jnp.asarray(qn))
    gt = exact_knn(q, x, K, metric="cosine")
    rec = np.mean([len(set(a.tolist()) & set(b.tolist())) / K
                   for a, b in zip(np.asarray(ids), gt)])
    assert rec > 0.99, f"flat sharded recall {rec}"

    # ---- pq: sharded ADC == single-host ADC ----
    pq = ProductQuantizer(PQConfig(m=8, k=32, iters=6))
    pq.train(jnp.asarray(x))
    codes = pq.encode(jnp.asarray(x))
    lut = build_adc_lut(jnp.asarray(q), pq.codebooks)
    fn_pq = make_pq_search(mesh, k=K, m_subspaces=8)
    d_sh, ids_sh = fn_pq(codes, lut)
    d_local, ids_local = pq.search(codes, jnp.asarray(q), K)
    np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_local),
                               rtol=1e-4, atol=1e-4)

    # ---- bq: sharded hamming == single-host ----
    bq = BinaryQuantizer(BQConfig(bits=64))   # 2 words: divisible by model=2
    bq.train(jnp.asarray(x))
    codes_b = bq.encode(jnp.asarray(x))
    q_codes = bq.encode(jnp.asarray(q))
    fn_bq = make_hamming_search(mesh, k=K, words=2)
    d_sh, ids_sh = fn_bq(codes_b, q_codes)
    d_loc, ids_loc = bq.search(codes_b, jnp.asarray(q), K)
    np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_loc),
                               rtol=1e-5, atol=1e-5)
    print("SUBPROCESS_OK")
""")

_TRAIN_PROG = _CHILD_PRELUDE + textwrap.dedent("""
    # ---- model train_step lowers + runs on 4x2 mesh ----
    from repro.configs import get_smoke_config
    from repro.models import init_train_state, make_train_step
    from repro.distributed.sharding import ShardingPolicy
    from repro.optim import AdamWConfig
    cfg = get_smoke_config("qwen2-1.5b").with_overrides(
        batch_axes=("data",))
    policy = ShardingPolicy(mesh)
    with mesh:
        state = jax.jit(lambda k: init_train_state(k, cfg))(
            jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, AdamWConfig(total_steps=5)))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "targets": jnp.ones((8, 16), jnp.int32),
                 "segment_ids": jnp.ones((8, 16), jnp.int32)}
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    print("SUBPROCESS_OK")
""")


def _run_child(prog: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + _ROOT
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "SUBPROCESS_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_sharded_search_multidevice():
    """Exact flat, PQ-ADC and Hamming shard_map scans on a 4x2 mesh."""
    _run_child(_SEARCH_PROG)


@pytest.mark.slow
def test_train_step_multidevice():
    """LLM train step on a 4x2 mesh (the training stack's own test)."""
    _run_child(_TRAIN_PROG)


def test_local_mesh_refuses_missing_devices():
    """A mesh larger than the device count raises instead of shrinking."""
    import jax
    from repro.launch.mesh import make_local_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(data=n + 1, model=1)
    assert make_local_mesh(data=n, model=1).devices.size == n


class TestShardingPolicy:
    def _policy(self):
        import jax
        from repro.distributed.sharding import ShardingPolicy
        from repro.launch.mesh import make_local_mesh
        return ShardingPolicy(make_local_mesh(1, 1))

    def test_divisibility_guard(self):
        from jax.sharding import PartitionSpec as P
        pol = self._policy()
        # model axis size 1 -> everything trivially divisible; spec exists
        spec = pol.param_spec("units/0/mlp/wg", (4, 64, 128))
        assert isinstance(spec, P)

    def test_row_parallel_names(self):
        pol = self._policy()
        spec = pol.param_spec("units/0/mlp/wd", (4, 128, 64))
        # contraction dim (ndim-2) gets the model axis (size 1 here -> ok)
        assert len(spec) == 3

    def test_batch_spec_skips_indivisible(self):
        pol = self._policy()
        assert pol.batch_spec((1, 5)) is not None


def test_production_mesh_shapes():
    """Mesh helper math (device-count-independent checks)."""
    from repro.launch.mesh import batch_axes

    class FakeMesh:
        axis_names = ("pod", "data", "model")

    assert batch_axes(FakeMesh()) == ("pod", "data")

    class FakeMesh2:
        axis_names = ("data", "model")

    assert batch_axes(FakeMesh2()) == ("data",)
