"""Training substrate + fault tolerance: convergence, µbatch equivalence,
checkpoint/restart, straggler watch, elastic remesh, serving engine."""

import os
import tempfile

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_mesh
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.configs import get_smoke
from repro.data import DataConfig, SyntheticLM
from repro.models import SINGLE_POD_PLAN
from repro.models import transformer as T
from repro.runtime import (FaultInjector, StragglerWatch, Supervisor, remesh,
                           scaled_microbatches, shardings_for)
from repro.serve import Request, ServeEngine
from repro.train import TrainSpec, adafactor, adamw, lr_schedule, make_train_step

PLAN = SINGLE_POD_PLAN


@pytest.fixture(scope="module")
def setup(request):
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke("llama3.2-1b")
    params, specs = T.init_params(jax.random.PRNGKey(0), cfg, PLAN)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=4))
    return mesh, cfg, params, specs, data


def test_loss_decreases_adamw(setup):
    mesh, cfg, params, _, data = setup
    opt = adamw(lr=1e-3)
    ts = jax.jit(make_train_step(cfg, PLAN, mesh, opt,
                                 TrainSpec(lr=1e-3, warmup_steps=5, total_steps=30)))
    o = opt.init(params)
    p = params
    losses = []
    for step in range(30):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        p, o, m = ts(p, o, batch, jnp.asarray(step))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3


def test_adafactor_also_trains(setup):
    mesh, cfg, params, _, data = setup
    opt = adafactor(lr=3e-3)
    ts = jax.jit(make_train_step(cfg, PLAN, mesh, opt,
                                 TrainSpec(lr=3e-3, warmup_steps=5, total_steps=20)))
    o = opt.init(params)
    p = params
    losses = []
    for step in range(20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        p, o, m = ts(p, o, batch, jnp.asarray(step))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_microbatch_grad_equivalence(setup):
    """mb=1 vs mb=2 must produce (nearly) the same update."""
    mesh, cfg, params, _, data = setup
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    def run_with(mb):
        opt = adamw(lr=1e-3)
        ts = jax.jit(make_train_step(cfg, PLAN, mesh, opt, TrainSpec(microbatches=mb)))
        p, o, m = ts(params, opt.init(params), batch, jnp.asarray(0))
        return p

    outs = [run_with(mb) for mb in (1, 2)]
    d = jax.tree.map(lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                                - b.astype(jnp.float32)).max()),
                     outs[0], outs[1])
    assert max(jax.tree.leaves(d)) < 5e-2


def test_lr_schedule_shapes():
    spec = TrainSpec(lr=1.0, warmup_steps=10, total_steps=100, schedule="wsd")
    assert float(lr_schedule(spec, jnp.asarray(0))) == 0.0
    assert float(lr_schedule(spec, jnp.asarray(10))) == 1.0
    assert float(lr_schedule(spec, jnp.asarray(50))) == 1.0
    assert float(lr_schedule(spec, jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)


def test_data_pipeline_deterministic_resume():
    d1 = SyntheticLM(DataConfig(vocab=100, seq_len=16, global_batch=2, seed=7))
    d2 = SyntheticLM(DataConfig(vocab=100, seq_len=16, global_batch=2, seed=7))
    np.testing.assert_array_equal(d1.batch(13)["tokens"], d2.batch(13)["tokens"])
    assert not np.array_equal(d1.batch(13)["tokens"], d1.batch(14)["tokens"])


# ------------------------------------------------------------- checkpointing

def test_checkpoint_roundtrip_bf16():
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32), "step": jnp.asarray(3)}}
    with tempfile.TemporaryDirectory() as d:
        save(d, 5, tree)
        assert latest_step(d) == 5
        got, manifest = restore(d, template=tree)
        assert manifest["step"] == 5
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_supervisor_restart_resumes_training(setup):
    mesh, cfg, params, _, data = setup
    opt = adamw(lr=1e-3)
    ts = jax.jit(make_train_step(cfg, PLAN, mesh, opt, TrainSpec()))

    def step_fn(state, step):
        p, o = state
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        p, o, m = ts(p, o, batch, jnp.asarray(step))
        return (p, o), m

    with tempfile.TemporaryDirectory() as d:
        inj = FaultInjector(schedule={7: "crash", 15: "crash"})
        sup = Supervisor(d, ckpt_every=5, injector=inj)
        res = sup.run((params, opt.init(params)), step_fn, total_steps=20)
        assert res.final_step == 20
        assert res.restarts == 2
        steps = [h["step"] for h in res.metrics_history]
        assert steps.count(5) >= 2        # step 5 replayed after the crash at 7


def test_straggler_watch_fires():
    w = StragglerWatch(deadline_multiple=2.0)
    fired = []
    for step, dt in enumerate([1.0, 1.0, 1.0, 5.0, 1.0]):
        w.observe(step, dt, on_straggler=lambda s, d, e: fired.append(s))
    assert fired == [3]
    assert len(w.events) == 1


def test_elastic_remesh_roundtrip(setup):
    mesh, cfg, params, specs, _ = setup
    new_mesh = make_mesh((1, 1), ("data", "model"))
    moved = remesh(params, specs, new_mesh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(moved)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert scaled_microbatches(2, old_dp=16, new_dp=8) == 4


# ------------------------------------------------------------------- serving

def test_serve_engine_continuous_batching(setup):
    mesh, cfg, params, _, _ = setup
    eng = ServeEngine(cfg, PLAN, mesh, params, slots=2, s_max=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 5).astype(np.int32),
                    max_new=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_ticks=200)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 4 for r in reqs)
    assert all(0 <= t < cfg.vocab for r in reqs for t in r.out)


def test_serve_greedy_matches_decode_loop(setup):
    mesh, cfg, params, _, _ = setup
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    eng = ServeEngine(cfg, PLAN, mesh, params, slots=1, s_max=32)
    r = Request(rid=0, prompt=prompt, max_new=3)
    eng.submit(r)
    eng.run_until_drained(max_ticks=64)
    # manual greedy decode
    state, _ = T.init_decode_state(cfg, PLAN, 1, 32)
    toks = list(prompt)
    outs = []
    for t in range(len(prompt) + 3 - 1):
        inp = jnp.asarray([[toks[t] if t < len(toks) else outs[-1]]], jnp.int32)
        state, lg = T.decode_step(params, cfg, PLAN, mesh, state, inp)
        if t >= len(prompt) - 1:
            nxt = int(jnp.argmax(lg[0, 0]))
            outs.append(nxt)
            if t >= len(toks) - 1:
                toks.append(nxt)
    assert r.out == outs[:3]


def test_serve_run_until_drained_returns_finished(setup):
    """Regression: ``run_until_drained`` must hand back every completed
    request exactly once, in completion order — it used to return [] always
    (finished requests were dropped on slot free)."""
    mesh, cfg, params, _, _ = setup
    eng = ServeEngine(cfg, PLAN, mesh, params, slots=2, s_max=64)
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                    max_new=2 + i) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_drained(max_ticks=200)
    assert [r.rid for r in finished] == [0, 1, 2, 3]   # shorter gens land first
    assert all(r.done for r in finished)
    assert eng.run_until_drained(max_ticks=1) == []    # exactly-once harvest
    with pytest.raises(ValueError, match="already in flight"):
        eng.submit(reqs[0])
        eng.submit(reqs[0])                            # duplicate rid is loud


def test_serve_tick_accounting_samples_prefill_final_logits(setup):
    """The engine docstring's contract: prefill and decode share the tick,
    the prefill-final logits are sampled (not discarded), so a request takes
    exactly ``len(prompt) + max_new - 1`` ticks for ``max_new`` tokens."""
    mesh, cfg, params, _, _ = setup
    eng = ServeEngine(cfg, PLAN, mesh, params, slots=1, s_max=32)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    r = Request(rid=0, prompt=prompt, max_new=4)
    eng.submit(r)
    ticks = 0
    while not r.done:
        eng.step()
        ticks += 1
        assert ticks < 64
    assert ticks == len(prompt) + r.max_new - 1
    assert len(r.out) == r.max_new
    # first output token appears on tick len(prompt): the tick that feeds
    # the last prompt token also samples from its logits
    eng2 = ServeEngine(cfg, PLAN, mesh, params, slots=1, s_max=32)
    r2 = Request(rid=0, prompt=prompt, max_new=4)
    eng2.submit(r2)
    for _ in range(len(prompt) - 1):
        eng2.step()
    assert r2.out == []                 # still prefilling
    eng2.step()
    assert len(r2.out) == 1             # prefill-final tick sampled
