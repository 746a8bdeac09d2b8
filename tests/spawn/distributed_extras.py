import os
# XLA_FLAGS provided by conftest
import sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline_par import pipeline_forward
from repro.distributed.collectives import int8_psum, compressed_grad_sync
import functools

# --- pipeline parallelism: 4 stages, stage i adds w[i] and doubles ---
mesh = make_mesh((4,), ("pipe",))
n_micro, mb, d = 8, 2, 16
x = jax.random.normal(jax.random.PRNGKey(0), (n_micro, mb, d))
w = jnp.arange(1.0, 5.0)[:, None] * jnp.ones((4, d))

def stage_fn(params, x):
    return x * 2.0 + params

got = pipeline_forward(stage_fn, w, x, mesh, axis="pipe")
want = x
for i in range(4):
    want = want * 2.0 + w[i]
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
print("pipeline_forward OK")

# --- int8 gradient all-reduce ---
mesh8 = make_mesh((8,), ("data",))
g_local = jax.random.normal(jax.random.PRNGKey(1), (8, 1024)) * 0.01

@functools.partial(jax.shard_map, mesh=mesh8, in_specs=P("data"), out_specs=P("data"))
def sync(g):
    return int8_psum(g[0], "data")[None] / 8.0

synced = sync(g_local)
want = jnp.mean(g_local, axis=0)
err = float(jnp.max(jnp.abs(synced[0] - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
assert err < 0.02, f"int8 psum relative error too high: {err}"
# every shard sees the same result
np.testing.assert_allclose(np.asarray(synced[0]), np.asarray(synced[3]), rtol=1e-6)
print(f"int8_psum OK (rel err {err:.4f})")

# --- error feedback reduces bias over repeated syncs ---
grads = {"w": jax.random.normal(jax.random.PRNGKey(2), (512,)) * 0.01}
ef = None
accum_plain = jnp.zeros((512,))
accum_ef = jnp.zeros((512,))
for step in range(8):
    synced, ef = compressed_grad_sync(grads, mesh8, "data", error_feedback=ef)
    accum_ef = accum_ef + synced["w"]
    plain, _ = compressed_grad_sync(grads, mesh8, "data", error_feedback=None)
    accum_plain = accum_plain + plain["w"]
true = grads["w"] * 8
err_ef = float(jnp.linalg.norm(accum_ef - true))
err_plain = float(jnp.linalg.norm(accum_plain - true))
assert err_ef <= err_plain * 1.05, (err_ef, err_plain)
print(f"error feedback OK (ef={err_ef:.5f} <= plain={err_plain:.5f})")
print("ALL DISTRIBUTED EXTRAS PASS")
