"""TPU-side fabric microbenchmark (the beyond-paper layer): MoE dispatch as a
SPAC switch — capacity (VOQ depth) vs drop-rate curve, payload compression
ratio, and hash-vs-learned routing balance.  CPU timings are indicative only;
the byte counts are exact."""

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, timed


def run():
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig, ShardingPlan
    from repro.models.moe import MoEOptions, apply_moe, init_moe
    from repro.kernels.quant_pack.ops import compression_ratio

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(name="bench", family="moe", n_layers=1, d_model=512,
                      n_heads=8, n_kv_heads=4, d_ff=1024, vocab=1000,
                      moe_experts=16, moe_topk=2)
    plan = ShardingPlan()
    params, _ = init_moe(jax.random.PRNGKey(0), cfg, plan)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128, 512), jnp.bfloat16)

    def moe_metric(opts, key):
        # one jit per static MoE config (building it in the loop body would
        # also late-bind the loop variable into the closure)
        return jax.jit(lambda p, xx: apply_moe(p, cfg, plan, mesh, xx,
                                               opts)[1][key])

    # VOQ sizing curve: capacity factor vs token drop rate (Alg.1 stage-3 analog)
    for cf in (0.5, 0.75, 1.0, 1.5, 2.0):
        fn = moe_metric(MoEOptions(capacity_factor=cf), "drop_frac")
        drop, us = timed(fn, params, x, repeats=2)
        emit(f"moe_fabric/capacity_{cf}", us, f"token_drop_rate={float(drop):.4f}")

    # payload protocol: wire bytes per dispatched token
    d = cfg.d_model
    bf16_bytes = d * 2
    int8_bytes = d + d // 128 * 4
    emit("moe_fabric/payload", 0.0,
         f"bf16={bf16_bytes}B/token int8={int8_bytes}B/token "
         f"ratio={bf16_bytes/int8_bytes:.2f}x "
         f"(kernel ratio={compression_ratio(jnp.zeros((128, d), jnp.bfloat16)):.2f}x)")

    # routing balance: learned vs hash (MultiBankHash analog)
    for router in ("learned_topk", "hash"):
        fn = moe_metric(MoEOptions(router=router), "expert_load")
        load, us = timed(fn, params, x, repeats=2)
        load = np.asarray(load, float)
        cov = load.std() / load.mean()
        emit(f"moe_fabric/router_{router}", us,
             f"load_cv={cov:.3f} max_share={load.max()/load.sum():.3f}")
    return True


if __name__ == "__main__":
    run()
