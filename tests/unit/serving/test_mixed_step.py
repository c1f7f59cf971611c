"""The unified mixed step vs the two-program engine it replaced.

Acceptance contract of the rewrite: with ``ServingConfig.mixed_step=True``
(the default) the engine serves every mix — shared-prefix traffic,
preemption storms, chaos drills — through ONE resident compiled program
with zero recompiles, token-identical to the legacy two-program engine
(``mixed_step=False``, whose only users are these parity tests: ROADMAP
D2)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def llama_engine():
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    return ds.init_inference(model, params=params, dtype="fp32")


def _run_both(llama_engine, prompts, new_tokens, **cfg_over):
    """Same traffic through the unified and the legacy engine; returns
    ``{mixed: {rid_index: tokens}}`` plus both engines for inspection."""
    outs, engines = {}, {}
    for mixed in (True, False):
        srv = ServingEngine(llama_engine, ServingConfig(
            mixed_step=mixed, **cfg_over))
        rids = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new_tokens)]
        res = srv.run()
        outs[mixed] = [(res[r].state, res[r].tokens) for r in rids]
        srv.block_pool.check_consistent()
        assert srv.block_pool.used_count == 0, "leaked blocks"
        engines[mixed] = srv
    return outs, engines


def test_shared_prefix_token_identical_to_two_program_engine(llama_engine):
    """Shared-prefix mixed traffic (cache hits, chunked prefill, decode)
    is token-identical across the engines, with exactly ONE resident
    compile and zero recompiles on the unified one."""
    vocab = llama_engine.module.config.vocab_size
    rs = np.random.RandomState(7)
    prefix = rs.randint(1, vocab, 24)
    prompts = [np.concatenate([prefix, rs.randint(1, vocab, int(t))])
               for t in (3, 7, 2, 9, 5)]
    prompts += [rs.randint(1, vocab, int(n)) for n in (4, 18, 11)]
    new = [5, 4, 7, 3, 6, 8, 4, 5]
    outs, engines = _run_both(
        llama_engine, prompts, new,
        max_batch_size=4, block_size=8, num_blocks=48, max_model_len=64,
        prefix_cache=True, prefill_chunk_tokens=8, prefill_token_budget=16)
    assert outs[True] == outs[False], "unified step diverged from legacy"
    assert all(s == "finished" for s, _ in outs[True])
    assert engines[True].compile_counts == {"mixed_step": 1}
    assert engines[True].perf.recompile_total == 0
    # the legacy engine really is the two-program one (the A/B is honest)
    assert engines[False].compile_counts == {"decode": 1, "prefill": 0,
                                             "chunked_prefill": 1}
    # both served cache hits
    assert engines[True].metrics.prefix_hits > 0
    assert engines[True].metrics.prefix_hits == \
        engines[False].metrics.prefix_hits


@pytest.mark.slow  # test_prefix_caching keeps the fast preemption parity
def test_preemption_token_identical_to_two_program_engine(llama_engine):
    """A pool sized to force eviction mid-generation: recompute-style
    resume through the packed step stays token-identical to the legacy
    engine under the same pressure."""
    vocab = llama_engine.module.config.vocab_size
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, vocab, int(n)) for n in (17, 21, 14)]
    outs, engines = _run_both(
        llama_engine, prompts, [10, 10, 10],
        max_batch_size=3, block_size=8, num_blocks=7, max_model_len=64,
        prefix_cache=True, prefill_chunk_tokens=16)
    assert outs[True] == outs[False]
    assert engines[True].metrics.preemptions > 0, \
        "pool sized to force preemption"
    assert engines[True].compile_counts == {"mixed_step": 1}


def test_chaos_storm_one_compile_sentinel_armed(llama_engine, monkeypatch):
    """The chaos-suite invariant on the unified engine: a probabilistic
    fault storm leaves every request terminal with zero leaks, the ONE
    compile intact, and the recompile sentinel armed-and-silent — faults
    are data, never shapes."""
    from deepspeed_tpu.utils import fault_injection

    vocab = llama_engine.module.config.vocab_size
    rs = np.random.RandomState(13)
    srv = ServingEngine(llama_engine, ServingConfig(
        max_batch_size=2, block_size=8, num_blocks=24, max_model_len=64,
        prefix_cache=True, prefill_chunk_tokens=8, step_watchdog_s=0.5))
    warm = srv.submit(rs.randint(1, vocab, 9), max_new_tokens=2)
    srv.run()
    assert srv.poll(warm).state == "finished"
    monkeypatch.setenv(fault_injection.ENV_VAR,
                       "flaky_prefill:p=0.25,corrupt_logits:p=0.15,"
                       "slow_step:p=0.2:seconds=0.02,"
                       "slow_chunk:p=0.1:seconds=0.02")
    fault_injection.reset()
    try:
        rids = [srv.submit(rs.randint(1, vocab, int(n)), max_new_tokens=3,
                           deadline_s=None if i % 3 else 10.0)
                for i, n in enumerate(rs.randint(2, 20, 12))]
        steps = 0
        while srv.has_work():
            srv.step()
            steps += 1
            assert steps < 500, "engine wedged under chaos"
    finally:
        monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
        fault_injection.reset()
    states = {srv.poll(r).state for r in rids}
    assert states <= {"finished", "failed", "timeout"}
    srv.block_pool.check_consistent()
    assert srv.block_pool.used_count == 0
    assert srv.compile_counts == {"mixed_step": 1}
    assert srv.perf.recompile_total == 0
    # recovery: fresh traffic after the storm rides the same compile
    r = srv.submit(rs.randint(1, vocab, 7), max_new_tokens=2)
    srv.run()
    assert srv.poll(r).state == "finished"
    assert srv.compile_counts == {"mixed_step": 1}


def test_prefill_grant_planning_round_robin():
    """plan_prefill_grants: chunk-granular round-robin in admission order,
    contiguous accumulation, budget-bounded, pure (no state changes)."""
    from deepspeed_tpu.inference.serving.block_pool import BlockPool
    from deepspeed_tpu.inference.serving.scheduler import (Request,
                                                           RequestState,
                                                           Scheduler)

    sched = Scheduler(4, BlockPool(16, 8), 8)
    reqs = []
    for i, owed in enumerate((20, 6, 3)):
        r = Request(prompt=list(range(1, owed + 1)), max_new_tokens=2)
        r.state = RequestState.RUNNING
        r.slot = i
        r.prefill_target = owed
        r.admit_order = i
        sched.slots[i] = r
        reqs.append(r)
    # budget 16, chunk 4: round 1 gives 4/4/3, round 2 gives req0 another
    # 4 and req1 the last 1 — contiguous accumulation, admission order
    grants = sched.plan_prefill_grants(16, 4)
    assert grants == {reqs[0].rid: 8, reqs[1].rid: 5, reqs[2].rid: 3}
    assert sum(grants.values()) == 16
    # planning changed nothing
    assert all(r.prefill_done == 0 for r in reqs)
    # budget beyond what is owed stops at owed
    assert sched.plan_prefill_grants(100, 8) == \
        {reqs[0].rid: 20, reqs[1].rid: 6, reqs[2].rid: 3}
    assert sched.plan_prefill_grants(0, 4) == {}


def test_packed_step_bounds_and_budget_metrics(llama_engine):
    """The packed batch honors its compiled capacity
    (max_batch_size - 1 + budget) and the renamed backlog gauges
    (prefill_waiting / prefill_queue_age_s) track the packed budget."""
    vocab = llama_engine.module.config.vocab_size
    rs = np.random.RandomState(11)
    srv = ServingEngine(llama_engine, ServingConfig(
        max_batch_size=2, block_size=8, num_blocks=24, max_model_len=64,
        prefill_chunk_tokens=4, prefill_token_budget=8))
    assert srv.mixed_step_tokens == 2 - 1 + 8
    long = srv.submit(rs.randint(1, vocab, 40), max_new_tokens=2)
    short = srv.submit(rs.randint(1, vocab, 4), max_new_tokens=12)
    waiting_seen = 0
    while srv.has_work():
        srv.step()
        waiting_seen = max(waiting_seen, srv.metrics.prefill_waiting)
        assert srv.metrics.prefill_queue_age_s >= 0.0
    assert waiting_seen >= 1          # the long prompt queued for budget
    assert srv.poll(long).state == "finished"
    assert srv.poll(short).state == "finished"
    assert srv.compile_counts == {"mixed_step": 1}
