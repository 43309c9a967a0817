"""SPMD-layer tests (multi-device): run in subprocesses with forced devices."""

import pytest

from conftest import run_subprocess_devices


def test_accumulate_modes_spmd():
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import accumulate
from repro.core.compat import make_mesh, shard_map
mesh = make_mesh((4, 2), ("data", "model"))
V = 64
x = jnp.arange(4 * V, dtype=jnp.float32).reshape(4, V)
expect = np.sum(np.asarray(x), axis=0)
for mode in ["gather_all", "reduce_scatter", "hierarchical"]:
    f = shard_map(lambda v: accumulate(v[0], "data", mode, inner_axis="data")[None],
                  mesh=mesh, in_specs=P("data", None), out_specs=P("data", None), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x))[0], expect, rtol=1e-6)
xs = np.zeros((4, V), np.float32)
for i in range(4): xs[i, i*3:i*3+2] = i + 1.0
for mode, inp, exp in [("sparse", jnp.asarray(xs), xs.sum(0)), ("auto", x, expect)]:
    f = shard_map(lambda v: accumulate(v[0], "data", mode, k=8)[None],
                  mesh=mesh, in_specs=P("data", None), out_specs=P("data", None), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(inp))[0], exp, rtol=1e-6)
print("SPMD_ACCUM_OK")
""")
    assert "SPMD_ACCUM_OK" in out


def test_zero1_matches_replicated_adamw():
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import adamw, zero1_init, zero1_update
from repro.core.dsm import pack_spec
from repro.core.compat import axis_size, make_mesh, shard_map
mesh = make_mesh((8,), ("data",))
params = {"w": jnp.ones((13, 7), jnp.bfloat16), "b": jnp.zeros((5,), jnp.bfloat16)}
spec = pack_spec(params)
opt = adamw(lr=0.1, weight_decay=0.0)
grads = [{"w": jnp.full((13,7), float(i+1), jnp.float32), "b": jnp.full((5,), .5*(i+1), jnp.float32)} for i in range(8)]
mean_g = jax.tree.map(lambda *g: sum(g)/8.0, *grads)
st = opt.init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
upd, _ = opt.update(mean_g, st, jax.tree.map(lambda p: p.astype(jnp.float32), params), 0)
ref = jax.tree.map(lambda p, u: p.astype(jnp.float32) + u, params, upd)
gstack = jax.tree.map(lambda *g: jnp.stack(g), *grads)
def step(gs):
    g = jax.tree.map(lambda x: x[0], gs)
    zst = zero1_init(params, opt, axis_size("data"), jax.lax.axis_index("data"), spec)
    newp, _ = zero1_update(g, zst, opt, "data", spec)
    return jax.tree.map(lambda x: x[None], newp)
f = jax.jit(shard_map(step, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
got = jax.tree.map(lambda x: np.asarray(x[0], np.float32), f(gstack))
for k in ("w", "b"):
    np.testing.assert_allclose(got[k], np.asarray(ref[k]), rtol=2e-2, atol=2e-2)
print("ZERO1_OK")
""")
    assert "ZERO1_OK" in out


def test_analytics_spmd_paths():
    out = run_subprocess_devices("""
import numpy as np, jax
from repro.data import logreg_dataset, powerlaw_graph
from repro.analytics import logreg, pagerank
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4)
x, y, _ = logreg_dataset(400, 24, seed=0)
ref = logreg.fit_reference(x, y, iters=8, lr=1e-3)
sp, _ = logreg.fit(x, y, iters=8, lr=1e-3, mesh=mesh, backend="spmd")
np.testing.assert_allclose(sp, ref, rtol=1e-4, atol=1e-5)
edges = powerlaw_graph(300, 5, seed=3)
rr = pagerank.fit_reference(edges, 300, iters=8)
rs, _ = pagerank.fit(edges, 300, iters=8, mode="reduce_scatter", mesh=mesh,
                     backend="spmd")
np.testing.assert_allclose(rs, rr, rtol=1e-4, atol=1e-6)
print("ANALYTICS_SPMD_OK")
""", n_devices=4)
    assert "ANALYTICS_SPMD_OK" in out


def test_compressed_accumulate_error_feedback():
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compressed_accumulate, ef_init
from repro.core.compat import make_mesh, shard_map
mesh = make_mesh((4,), ("data",))
V, k = 512, 64
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(4, V)), jnp.float32)
def step(gs):
    ef = ef_init(V)
    total, ef2 = compressed_accumulate(gs[0], ef, "data", k)
    return total[None], ef2.residual[None]
f = jax.jit(shard_map(step, mesh=mesh, in_specs=P("data", None),
                      out_specs=(P("data", None), P("data", None)), check_vma=False))
total, resid = f(g)
# per-device identity: sent + residual = corrected
print("EF_OK", float(jnp.sum(jnp.abs(total))) > 0)
""", n_devices=4)
    assert "EF_OK True" in out


def test_elastic_restore_across_mesh_sizes():
    """FT: checkpoint on a 4-way mesh, recover onto 2-way (multi-node recovery)
    and back onto 8-way (elastic scale-up) — values identical everywhere."""
    out = run_subprocess_devices("""
import tempfile, jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.ft import save_checkpoint, elastic_restore
from repro.launch.mesh import _mk

tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
specs = {"w": P("data", None), "b": P()}
with tempfile.TemporaryDirectory() as d:
    m4 = _mk((4,), ("data",), devices=jax.devices()[:4])
    placed = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(m4, s)), tree, specs)
    save_checkpoint(d, 7, placed)
    # scale DOWN to 2 devices (node failure)
    m2 = _mk((2,), ("data",), devices=jax.devices()[:2])
    r2, _, step = elastic_restore(d, tree, m2, specs)
    assert step == 7
    np.testing.assert_allclose(np.asarray(r2["w"]), np.asarray(tree["w"]))
    assert len(r2["w"].sharding.device_set) == 2
    # scale UP to 8 devices (capacity returns)
    m8 = _mk((8,), ("data",))
    r8, _, _ = elastic_restore(d, tree, m8, specs)
    np.testing.assert_allclose(np.asarray(r8["w"]), np.asarray(tree["w"]))
    assert len(r8["w"].sharding.device_set) == 8
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_perf_knobs_preserve_numerics():
    """seq_shard / remat / block_k are layout-only: loss identical (fp tol)."""
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch, smoke_config
from repro.launch.mesh import _mk
from repro.launch import shardings as sh
from repro.models.build import build_model

mesh = _mk((2, 2), ("data", "model"))
sh.set_mesh_axis_sizes(mesh)
base = smoke_config(get_arch("qwen3-1.7b")).replace(batch_axes=("data",))
opt_cfgs = {
    "baseline": base,
    "sp": base.replace(seq_shard=True),
    "sp_dots_b128": base.replace(seq_shard=True, remat="dots", block_k=128),
    "full_remat": base.replace(remat="full"),
}
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, base.vocab, (4, 64)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, base.vocab, (4, 64)), jnp.int32)}
losses = {}
with mesh:
    for name, cfg in opt_cfgs.items():
        m = build_model(cfg, data_groups=2)
        p = m.init(jax.random.PRNGKey(0))
        loss, _ = jax.jit(m.loss_fn)(p, batch)
        losses[name] = float(loss)
ref = losses["baseline"]
for name, l in losses.items():
    np.testing.assert_allclose(l, ref, rtol=2e-5), name
print("KNOBS_EQUIV_OK", losses)
""")
    assert "KNOBS_EQUIV_OK" in out


def test_moe_ep_alltoall_matches_dense_oracle():
    """shard_map EP dispatch (all_to_all over the expert axis) == dense oracle."""
    out = run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import _mk
from repro.launch import shardings as sh
from repro.models.ffn import MoEConfig, init_moe, moe_ffn

mesh = _mk((2, 4), ("data", "model"))
sh.set_mesh_axis_sizes(mesh)
cfg_ep = MoEConfig(d_model=16, n_experts=8, top_k=2, d_ff_expert=8,
                   capacity_factor=8.0, impl="ep")
p = init_moe(jax.random.PRNGKey(0), cfg_ep)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(4, 16, 16)), jnp.float32)
with mesh:
    y_ep, aux_ep = jax.jit(lambda p, x: moe_ffn(p, x, cfg_ep))(p, x)
    y_d, aux_d = jax.jit(lambda p, x: moe_ffn(p, x, cfg_ep._replace(impl="dense")))(p, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_d), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(float(aux_ep), float(aux_d), rtol=0.25)
g = jax.jit(jax.grad(lambda p, x: moe_ffn(p, x, cfg_ep)[0].sum()))(p, x)
gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
assert gn > 0 and np.isfinite(gn)
print("EP_ORACLE_OK")
""")
    assert "EP_ORACLE_OK" in out
