"""End-to-end training driver: a ~100M-parameter llama-family model trained
for a few hundred steps on synthetic data with the full production substrate —
microbatched train step, WSD schedule, async checkpointing, fault-tolerant
supervisor (with an injected crash to prove restart), and exact data resume.

    pip install -e .   # once
    python examples/train_e2e.py --steps 200      # full run
    python examples/train_e2e.py --steps 20       # quick look
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_mesh

from repro.data import DataConfig, SyntheticLM
from repro.models import SINGLE_POD_PLAN, ModelConfig
from repro.models import transformer as T
from repro.runtime import FaultInjector, Supervisor
from repro.train import TrainSpec, adamw, make_train_step


def model_100m() -> ModelConfig:
    return ModelConfig(name="llama-100m", family="dense", n_layers=12,
                       d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
                       d_ff=2048, vocab=32000, rope_theta=1e4, remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--inject-crash", type=int, default=None,
                    help="step at which to kill the 'node' (default steps//2)")
    args = ap.parse_args()

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = model_100m()
    plan = SINGLE_POD_PLAN
    print(f"model: {cfg.name} — {cfg.param_count()/1e6:.0f}M params")

    params, _ = T.init_params(jax.random.PRNGKey(0), cfg, plan)
    opt = adamw(lr=6e-4)
    spec = TrainSpec(microbatches=2, lr=6e-4, warmup_steps=max(args.steps // 20, 2),
                     total_steps=args.steps, schedule="wsd")
    train_step = jax.jit(make_train_step(cfg, plan, mesh, opt, spec))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=0))

    def step_fn(state, step):
        p, o = state
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        p, o, m = train_step(p, o, batch, jnp.asarray(step))
        return (p, o), m

    crash_at = args.inject_crash if args.inject_crash is not None else args.steps // 2
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="spac_e2e_")
    sup = Supervisor(ckpt_dir, ckpt_every=max(args.steps // 8, 5),
                     injector=FaultInjector(schedule={crash_at: "crash"}))

    t0 = time.time()
    res = sup.run((params, opt.init(params)), step_fn, total_steps=args.steps)
    dt = time.time() - t0
    losses = [h["loss"] for h in res.metrics_history]
    n_tok = args.batch * args.seq
    print(f"\n{res.final_step} steps in {dt:.0f}s "
          f"({n_tok * len(losses) / dt:.0f} tok/s incl. {res.restarts} restart(s))")
    k = max(len(losses) // 10, 1)
    print(f"loss: {sum(losses[:k])/k:.3f} -> {sum(losses[-k:])/k:.3f}")
    print(f"checkpoints in {ckpt_dir}")
    assert sum(losses[-k:]) / k < sum(losses[:k]) / k, "training must make progress"


if __name__ == "__main__":
    main()
