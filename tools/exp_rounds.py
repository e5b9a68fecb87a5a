"""Per-goal round/moves/wall-clock breakdown at a given scale (host CPU).

Experiment harness for round-count work: prints one JSON line per goal plus
a summary line, so grid/width changes can be validated (rounds down, quality
pinned) before touching defaults.

    JAX_PLATFORMS=cpu python tools/exp_rounds.py [brokers] [partitions] [drain]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    num_brokers = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
    num_partitions = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
    drain = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    import jax

    from cruise_control_tpu import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    from cruise_control_tpu.analyzer.optimizer import (
        GoalOptimizer, goals_by_priority,
    )
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )
    from cruise_control_tpu.model.fixtures import Dist, random_cluster

    state, meta = random_cluster(
        num_brokers=num_brokers, num_topics=max(8, num_brokers // 10),
        num_partitions=num_partitions, rf=3, num_racks=8,
        dist=Dist.EXPONENTIAL, seed=42, skew_to_first=2.0,
        target_utilization=0.55)
    if drain:
        import jax.numpy as jnp

        from cruise_control_tpu.common.broker_state import BrokerState
        from cruise_control_tpu.model.tensors import set_broker_state
        state = set_broker_state(
            state, jnp.arange(num_brokers - drain, num_brokers),
            BrokerState.DEAD)
    state = jax.device_put(state)
    jax.block_until_ready(state.assignment)

    overrides = json.loads(os.environ.get("EXP_CONFIG", "{}"))
    cfg = CruiseControlConfig(overrides)
    optimizer = GoalOptimizer(cfg, mesh="auto")
    t0 = time.time()
    _, warm = optimizer.optimizations(state, meta,
                                      goals=goals_by_priority(cfg))
    warm_s = time.time() - t0
    t0 = time.time()
    _, res = optimizer.optimizations(state, meta,
                                     goals=goals_by_priority(cfg))
    steady_s = time.time() - t0
    for g in res.goal_results:
        print(json.dumps({"goal": g.name, "rounds": g.rounds,
                          "moves": g.moves_applied, "swaps": g.swaps_applied,
                          "duration_s": round(g.duration_s, 3),
                          "violation": round(g.residual_violation, 4)}),
              flush=True)
    print(json.dumps({
        "steady_s": round(steady_s, 3), "warm_s": round(warm_s, 3),
        "total_rounds": sum(g.rounds for g in res.goal_results),
        "total_moves": sum(g.moves_applied for g in res.goal_results),
        "num_proposals": len(res.proposals),
        "balancedness_after": round(res.balancedness_after, 2),
        "violated_goals_after": res.violated_goals_after,
        "overrides": overrides}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
