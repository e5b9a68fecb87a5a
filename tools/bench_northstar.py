"""Standalone north-star stage: 7,000 brokers / 1M partitions, full chain.

The driver's bench budget (840 s) ends at the 1k stages; this runner
measures BASELINE.md config #5 in isolation with no watchdog, printing the
same JSON line shape as bench.py so results can be pasted into BASELINE.md
/ BENCH notes. Run it SOLO: a chip belongs to one process at a time.

    python tools/bench_northstar.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    num_brokers = int(os.environ.get("NS_BROKERS", "7000"))
    num_partitions = int(os.environ.get("NS_PARTITIONS", "1000000"))
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

    device = jax.devices()[0].platform
    chips = jax.device_count()
    budget_s = 30.0 * (num_partitions / 1_000_000) * (8.0 / min(chips, 8))

    t0 = time.time()
    state, meta = random_cluster(
        num_brokers=num_brokers, num_topics=max(8, num_brokers // 10),
        num_partitions=num_partitions, rf=3, num_racks=8,
        dist=Dist.EXPONENTIAL, seed=42, skew_to_first=2.0,
        target_utilization=0.55)
    state = jax.device_put(state)
    jax.block_until_ready(state.assignment)
    build_s = time.time() - t0

    cfg = CruiseControlConfig()
    optimizer = GoalOptimizer(cfg, mesh="auto")
    t0 = time.time()
    _, warm = optimizer.optimizations(state, meta,
                                      goals=goals_by_priority(cfg))
    warm_s = time.time() - t0
    t0 = time.time()
    _, res = optimizer.optimizations(state, meta,
                                     goals=goals_by_priority(cfg))
    steady_s = time.time() - t0
    print(json.dumps({
        "metric": f"rebalance_proposal_wall_clock_{num_brokers}brokers_"
                  f"{num_partitions // 1000}kpartitions",
        "value": round(steady_s, 3), "unit": "s",
        "vs_baseline": round(budget_s / steady_s, 3),
        "extras": {
            "device": device, "solver_devices": optimizer.solver_devices(),
            "model_build_s": round(build_s, 3),
            "warmup_incl_compile_s": round(warm_s, 3),
            "num_proposals": len(res.proposals),
            "balancedness_after": round(res.balancedness_after, 2),
            "violated_goals_after": res.violated_goals_after,
            "total_rounds": sum(g.rounds for g in res.goal_results),
            "budget_s_prorated": round(budget_s, 3),
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
