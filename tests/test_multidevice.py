"""Multi-device semantics (MoE fabric sharding, compressed pod protocol,
dry-run smoke) — run in subprocesses so the main session keeps 1 device."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_moe_fabric_sharded_equals_single_device():
    """The switch-fabric MoE must be invariant to the mesh layout."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig, ShardingPlan
from repro.models.moe import init_moe, apply_moe, MoEOptions
cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=128, n_heads=4,
                  n_kv_heads=2, d_ff=256, vocab=512, moe_experts=8, moe_topk=2,
                  capacity_factor=8.0)   # no drops -> layouts must agree exactly
plan = ShardingPlan()
params, _ = init_moe(jax.random.PRNGKey(0), cfg, plan)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 128), jnp.float32).astype(jnp.bfloat16)
outs = []
for shape in [(1, 1), (2, 4), (4, 2), (8, 1)]:
    mesh = make_mesh(shape, ("data", "model"))
    y, aux = apply_moe(params, cfg, plan, mesh, x)
    outs.append(np.asarray(y.astype(jnp.float32)))
for o in outs[1:]:
    np.testing.assert_allclose(outs[0], o, atol=3e-2)
print("fabric mesh-invariant OK")
""")


def test_compressed_pod_protocol_close_to_exact_mean():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.comm.protocols import compressed_mean
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
g = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 256), jnp.float32)

def f(g):
    # the pod-sharded input already differs per pod member
    local = g * (1.0 + jnp.abs(g).mean())          # pod-varying gradients
    exact = jax.lax.pmean(local, "pod")
    comp = compressed_mean({"g": local}, "pod")["g"]
    return exact, comp
exact, comp = jax.jit(jax.shard_map(f, mesh=mesh, axis_names={"pod"},
                                    in_specs=P("pod"), out_specs=(P(), P()),
                                    check_vma=False))(g)
err = float(jnp.abs(exact - comp).max())
scale = float(jnp.abs(exact).max())
assert err < 0.02 * scale, (err, scale)
print("compressed pod mean OK", err)
""")


def test_train_step_with_compressed_pod_grads_runs():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_smoke
from repro.models.config import MULTI_POD_PLAN
from repro.models import transformer as T
from repro.train import adamw, make_train_step, TrainSpec
from repro.data import DataConfig, SyntheticLM
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_smoke("llama3.2-1b")
plan = MULTI_POD_PLAN
params, _ = T.init_params(jax.random.PRNGKey(0), cfg, plan)
opt = adamw(lr=1e-3)
for compress in (False, True):
    ts = jax.jit(make_train_step(cfg, plan, mesh, opt,
                                 TrainSpec(compress_pod_grads=compress)))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    p, o, m = ts(params, opt.init(params), batch, jnp.asarray(0))
    assert np.isfinite(float(m["loss"]))
print("compressed-grad train step OK")
""")


def test_dryrun_single_cell_smoke():
    """The actual dry-run entry point on the 512-device production mesh."""
    out = _run("""
import sys
sys.argv = ["dryrun", "--arch", "llama3.2-1b", "--shape", "decode_32k",
            "--mesh", "single", "--out", "/tmp/test_dryrun"]
import shutil; shutil.rmtree("/tmp/test_dryrun", ignore_errors=True)
from repro.launch.dryrun import main
try:
    main()
except SystemExit as e:
    assert e.code == 0, "dry-run cell failed"
""", devices=512)
