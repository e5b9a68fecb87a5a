"""Chained-marginal per-round cost profile of the chain search kernel.

VERDICT r3 #3: attribute the ~46 ms/round device cost at 1k brokers.
Every number here is a MARGINAL: run the fused driver for k and 2k
rounds and report (t2k - tk) / k, so the fixed per-dispatch cost
(enqueue, readback, dispatch glue) cancels.

    python tools/profile_round.py [brokers] [partitions] [goal_index]
"""

from __future__ import annotations

import sys
import time

import _common


def main() -> int:
    num_brokers = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
    num_partitions = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
    import jax
    import jax.numpy as jnp

    _common.enable_cache()
    from cruise_control_tpu.analyzer.chain import chain_optimize_rounds
    from cruise_control_tpu.analyzer.optimizer import (
        GoalOptimizer, goals_by_priority,
    )
    from cruise_control_tpu.analyzer.search import ExclusionMasks
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )
    from cruise_control_tpu.model.fixtures import Dist, random_cluster

    print(f"platform: {jax.devices()[0].platform}", flush=True)
    state, meta = random_cluster(
        num_brokers=num_brokers, num_topics=max(8, num_brokers // 10),
        num_partitions=num_partitions, rf=3, num_racks=8,
        dist=Dist.EXPONENTIAL, seed=42, skew_to_first=2.0,
        target_utilization=0.55)
    state = jax.device_put(state)
    jax.block_until_ready(state.assignment)

    cfg = CruiseControlConfig()
    optimizer = GoalOptimizer(cfg)
    scfg = optimizer.search_config(state)
    goals = tuple(goals_by_priority(cfg))
    masks = ExclusionMasks()
    constraint = optimizer.constraint

    def run(goal_idx: int, budget: int, cfg_used):
        prior = jnp.asarray([j < goal_idx for j in range(len(goals))])
        st, moves, rounds = chain_optimize_rounds(
            state, jnp.int32(goal_idx), prior, goals, constraint, cfg_used,
            meta.num_topics, masks, budget=jnp.int32(budget))
        jax.block_until_ready(st.assignment)
        return int(rounds)

    def marginal(goal_idx: int, cfg_used, k: int = 8) -> tuple[float, int]:
        run(goal_idx, 1, cfg_used)            # compile + warm
        t0 = time.monotonic(); r1 = run(goal_idx, k, cfg_used)
        t1 = time.monotonic(); r2 = run(goal_idx, 2 * k, cfg_used)
        t2 = time.monotonic()
        extra_rounds = max(1, r2 - r1)
        return ((t2 - t1) - (t1 - t0)) / extra_rounds, r2

    from dataclasses import replace
    wide = replace(scfg, num_sources=min(2048, scfg.num_sources * 4),
                   moves_per_round=min(2048, scfg.moves_per_round * 2))
    for goal_idx in (0, 6, 9, 12):   # rack, replica-count, nw-out-dist, topic
        name = goals[goal_idx].name
        per_round, r = marginal(goal_idx, scfg)
        print(f"goal[{goal_idx}] {name:42s} narrow({scfg.num_sources}) "
              f"~{per_round * 1000:7.1f} ms/round  (ran {r})", flush=True)
        per_round_w, rw = marginal(goal_idx, wide)
        print(f"goal[{goal_idx}] {name:42s} wide({wide.num_sources})   "
              f"~{per_round_w * 1000:7.1f} ms/round  (ran {rw})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
