"""Multi-device tests.  Each test shells out to a fresh interpreter with
XLA_FLAGS=--xla_force_host_platform_device_count=N so the main pytest
process keeps its single CPU device (see launch/dryrun.py note).
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_distributed_truss_matches_oracle():
    run_py("""
import numpy as np
from repro.core import GraphSpec, oracle
from repro.core.distributed import distributed_decompose
from repro.launch.mesh import make_test_mesh
from repro.data.synthetic import powerlaw_graph

edges = powerlaw_graph(60, 4, seed=5)
adj = {i: set() for i in range(60)}
for a, b in edges:
    adj[a].add(b); adj[b].add(a)
ref = oracle.truss_decomposition(adj)
spec = GraphSpec(n_nodes=60, d_max=60, e_cap=len(edges))
mesh = make_test_mesh((8,), ("data",))
for delta in (False, True):
    phi = distributed_decompose(spec, mesh, np.asarray(edges), delta=delta)
    got = {tuple(e): int(p) for e, p in zip(edges, phi)}
    assert got == ref, delta
print("ok")
""")


@pytest.mark.slow
def test_sharded_lm_train_step_runs():
    """Tiny LM train step executes (not just compiles) on a (2,4) mesh with
    the production sharding rules, and matches the single-device loss."""
    run_py("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.specs import build_lm_cell
from repro.configs.base import ShapeCell
import dataclasses

arch = get_config("qwen3-0.6b")
smoke_arch = dataclasses.replace(arch, model=arch.smoke,
    shapes=(ShapeCell("train_tiny", "train", {"batch": 4, "seq": 32}),))
mesh = make_test_mesh((2, 4), ("data", "model"))
plan = build_lm_cell(smoke_arch, smoke_arch.shapes[0], mesh)
jitted = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                 out_shardings=plan.out_shardings)

from repro.models import transformer
from repro.training.optimizer import adamw_init
params = transformer.init_params(arch.smoke, jax.random.PRNGKey(0))
opt = adamw_init(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, arch.smoke.vocab, (4, 32)), jnp.int32),
         "targets": jnp.asarray(rng.integers(0, arch.smoke.vocab, (4, 32)), jnp.int32)}
with mesh:
    p2, o2, stats = jitted(params, opt, batch)
sharded_loss = float(stats["loss"])

ref_loss = float(transformer.loss_fn(arch.smoke, params, batch))
assert abs(sharded_loss - ref_loss) < 0.05, (sharded_loss, ref_loss)
print("ok", sharded_loss, ref_loss)
""")


def test_compressed_psum_matches_fp32():
    run_py("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.training.compression import compressed_psum

mesh = make_test_mesh((4,), ("data",))
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 256)).astype(np.float32))
fn = jax.jit(jax.shard_map(lambda v: compressed_psum(v[0], "data"),
    mesh=mesh, in_specs=P("data", None), out_specs=P(), check_vma=False))
got = np.asarray(fn(x))
exp = np.asarray(x.sum(0))
err = np.abs(got - exp).max() / (np.abs(exp).max() + 1e-9)
assert err < 0.05, err
print("ok", err)
""")


def test_production_mesh_shapes():
    run_py("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh(multi_pod=False)
assert m1.axis_names == ("data", "model") and m1.devices.size == 256
m2 = make_production_mesh(multi_pod=True)
assert m2.axis_names == ("pod", "data", "model") and m2.devices.size == 512
print("ok")
""", devices=512)


def test_gnn_edge_sharded_step_matches_single_device():
    run_py("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.data import sampler, synthetic
from repro.models import gnn

cfg = get_config("gcn-cora").smoke
edges = synthetic.powerlaw_graph(64, 3, seed=1)
batch = sampler.make_gnn_batch(edges, 64, 8, n_classes=cfg.n_classes,
                               pad_edges=-(-2*len(edges)//8)*8, seed=2)
batch = {k: jnp.asarray(v) for k, v in batch.items()}
params = gnn.init_params(cfg, jax.random.PRNGKey(0), 8)
ref = float(gnn.loss_fn(cfg, params, batch))

mesh = make_test_mesh((8,), ("data",))
shardings = {k: NamedSharding(mesh, P("data", *([None]*(v.ndim-1))))
             if k.startswith("edge_") else NamedSharding(mesh, P())
             for k, v in batch.items()}
fn = jax.jit(lambda p, b: gnn.loss_fn(cfg, p, b),
             in_shardings=(None, shardings))
with mesh:
    got = float(fn(params, batch))
assert abs(got - ref) < 1e-4, (got, ref)
print("ok", got, ref)
""")
