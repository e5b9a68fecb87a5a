"""The merged framework configuration.

Reference parity: config/KafkaCruiseControlConfig.java (merges
MonitorConfig / AnalyzerConfig / ExecutorConfig / AnomalyDetectorConfig /
WebServerConfig / UserTaskManagerConfig constants and performs cross-field
sanity checks such as hard-goals ⊆ goals). Defaults follow
config/cruisecontrol.properties.

The goal class names here are dotted paths into
``cruise_control_tpu.analyzer.goals`` — the TPU-native goal kernels.
"""

from __future__ import annotations

from typing import Any, Mapping

from .abstract_config import AbstractConfig
from .configdef import ConfigDef, ConfigException, ConfigType, Importance, Range

_G = "cruise_control_tpu.analyzer.goals"

# Default goal chain: mirrors config/cruisecontrol.properties goals= order.
DEFAULT_GOALS = [
    f"{_G}.RackAwareGoal",
    f"{_G}.ReplicaCapacityGoal",
    f"{_G}.DiskCapacityGoal",
    f"{_G}.NetworkInboundCapacityGoal",
    f"{_G}.NetworkOutboundCapacityGoal",
    f"{_G}.CpuCapacityGoal",
    f"{_G}.ReplicaDistributionGoal",
    f"{_G}.PotentialNwOutGoal",
    f"{_G}.DiskUsageDistributionGoal",
    f"{_G}.NetworkInboundUsageDistributionGoal",
    f"{_G}.NetworkOutboundUsageDistributionGoal",
    f"{_G}.CpuUsageDistributionGoal",
    f"{_G}.TopicReplicaDistributionGoal",
    f"{_G}.LeaderReplicaDistributionGoal",
    f"{_G}.LeaderBytesInDistributionGoal",
]

DEFAULT_HARD_GOALS = [
    f"{_G}.RackAwareGoal",
    f"{_G}.ReplicaCapacityGoal",
    f"{_G}.DiskCapacityGoal",
    f"{_G}.NetworkInboundCapacityGoal",
    f"{_G}.NetworkOutboundCapacityGoal",
    f"{_G}.CpuCapacityGoal",
]

DEFAULT_ANOMALY_DETECTION_GOALS = [
    f"{_G}.RackAwareGoal",
    f"{_G}.ReplicaCapacityGoal",
    f"{_G}.DiskCapacityGoal",
]


def _definition() -> ConfigDef:
    d = ConfigDef()
    T, I = ConfigType, Importance

    # --- Monitor (MonitorConfig.java; defaults cruisecontrol.properties) ---
    d.define("bootstrap.servers", T.LIST, [], None, I.HIGH,
             "Kafka bootstrap servers for the managed cluster.")
    d.define("metric.sampling.interval.ms", T.LONG, 120_000, Range.at_least(1), I.HIGH,
             "Interval of metric sampling (default 120s).")
    d.define("partition.metrics.window.ms", T.LONG, 300_000, Range.at_least(1), I.HIGH,
             "Partition metrics window size.")
    d.define("num.partition.metrics.windows", T.INT, 5, Range.at_least(1), I.HIGH,
             "Number of partition windows kept.")
    d.define("broker.metrics.window.ms", T.LONG, 300_000, Range.at_least(1), I.HIGH,
             "Broker metrics window size.")
    d.define("num.broker.metrics.windows", T.INT, 20, Range.at_least(1), I.HIGH,
             "Number of broker windows kept.")
    d.define("min.samples.per.partition.metrics.window", T.INT, 1, Range.at_least(1), I.MEDIUM,
             "Minimum samples for a partition window to be valid.")
    d.define("min.samples.per.broker.metrics.window", T.INT, 1, Range.at_least(1), I.MEDIUM,
             "Minimum samples for a broker window to be valid.")
    d.define("min.valid.partition.ratio", T.DOUBLE, 0.95, Range.between(0, 1), I.HIGH,
             "Minimum monitored-valid partition ratio for model building.")
    d.define("max.allowed.extrapolations.per.partition", T.INT, 8, Range.at_least(0), I.LOW,
             "Max extrapolated windows tolerated per partition entity.")
    d.define("max.allowed.extrapolations.per.broker", T.INT, 8, Range.at_least(0), I.LOW,
             "Max extrapolated windows tolerated per broker entity.")
    d.define("prometheus.server.endpoint", T.STRING, None, None, I.LOW,
             "Prometheus base URL for PrometheusMetricSampler.from_endpoint "
             "(prometheus/PrometheusMetricSampler.java config).")
    d.define("metric.sampler.class", T.CLASS,
             "cruise_control_tpu.monitor.sampling.synthetic_sampler.SyntheticMetricSampler",
             None, I.HIGH, "Pluggable MetricSampler implementation.")
    d.define("sample.store.class", T.CLASS,
             "cruise_control_tpu.monitor.sampling.sample_store.FileSampleStore",
             None, I.MEDIUM, "Pluggable SampleStore implementation.")
    d.define("sample.store.path", T.STRING, "fileStore/samples", None, I.LOW,
             "Directory for the file-backed sample store.")
    d.define("num.metric.fetchers", T.INT, 1, Range.at_least(1), I.LOW,
             "Parallel metric fetcher workers.")
    d.define("broker.capacity.config.resolver.class", T.CLASS,
             "cruise_control_tpu.monitor.capacity.FileCapacityResolver",
             None, I.HIGH, "Pluggable broker capacity resolver.")
    d.define("capacity.config.file", T.STRING, "config/capacity.json", None, I.HIGH,
             "Capacity JSON file (DISK MB, CPU %, NW KB/s; JBOD maps).")
    d.define("monitor.state.update.interval.ms", T.LONG, 30_000, Range.at_least(1), I.LOW,
             "Monitor state refresh cadence.")
    d.define("metric.sampler.partition.assignor.class", T.CLASS,
             "cruise_control_tpu.monitor.sampling.fetcher.DefaultPartitionAssignor",
             None, I.LOW, "Partition→fetcher assignment policy.")
    d.define("fetch.metric.samples.max.retry.count", T.INT, 5,
             Range.at_least(0), I.LOW, "Sampling fetch retries per window.")
    d.define("skip.loading.samples", T.BOOLEAN, False, None, I.LOW,
             "Skip the warm-start sample replay at startup.")
    d.define("sampling.allow.cpu.capacity.estimation", T.BOOLEAN, True, None,
             I.LOW, "Estimate CPU capacity from cores when unset.")
    d.define("sample.partition.metric.store.on.execution.class", T.CLASS,
             None, None, I.LOW,
             "Extra store receiving samples gathered mid-execution.")
    d.define("use.linear.regression.model", T.BOOLEAN, False, None, I.LOW,
             "CPU estimation via the trained linear model instead of the "
             "static coefficients.")
    d.define("linear.regression.model.cpu.util.bucket.size", T.INT, 5,
             Range.between(1, 100), I.LOW,
             "CPU-utilization bucket width for training sample balance.")
    d.define("leader.network.inbound.weight.for.cpu.util", T.DOUBLE, 0.6,
             Range.at_least(0), I.LOW,
             "Static CPU model coefficient (ModelParameters.java).")
    d.define("leader.network.outbound.weight.for.cpu.util", T.DOUBLE, 0.1,
             Range.at_least(0), I.LOW, "Static CPU model coefficient.")
    d.define("follower.network.inbound.weight.for.cpu.util", T.DOUBLE, 0.3,
             Range.at_least(0), I.LOW, "Static CPU model coefficient.")
    d.define("topic.config.provider.class", T.CLASS, None, None, I.LOW,
             "Pluggable topic-config source (default: the admin backend).")
    d.define("zookeeper.security.enabled", T.BOOLEAN, False, None, I.LOW,
             "Legacy ZK flag; accepted for config parity, ZK paths are not "
             "implemented (metadata polling replaces the ZK watcher).")
    d.define("failed.brokers.zk.path", T.STRING, None, None, I.LOW,
             "Legacy ZK persistence path; the file store replaces it.")
    d.define("network.client.provider.class", T.CLASS, None, None, I.LOW,
             "Network client factory override (reference plumbing; the "
             "wire binding manages its own connections).")

    # --- Analyzer (AnalyzerConfig.java) ---
    d.define("goals", T.LIST, list(DEFAULT_GOALS), None, I.HIGH,
             "Default goal chain, priority order.")
    d.define("hard.goals", T.LIST, list(DEFAULT_HARD_GOALS), None, I.HIGH,
             "Goals that must always be satisfied.")
    d.define("default.goals", T.LIST, [], None, I.MEDIUM,
             "Goals used for precomputed proposals (empty = goals).")
    d.define("anomaly.detection.goals", T.LIST, list(DEFAULT_ANOMALY_DETECTION_GOALS), None,
             I.MEDIUM, "Goals replayed by the goal-violation detector.")
    d.define("cpu.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for CPU.")
    d.define("disk.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for disk.")
    d.define("network.inbound.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for NW in.")
    d.define("network.outbound.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for NW out.")
    d.define("replica.count.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for replica counts.")
    d.define("leader.replica.count.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for leader replica counts.")
    d.define("topic.replica.count.balance.threshold", T.DOUBLE, 1.1, Range.at_least(1), I.MEDIUM,
             "Balance band multiplier for per-topic replica counts.")
    d.define("cpu.capacity.threshold", T.DOUBLE, 0.7, Range.between(0, 1), I.MEDIUM,
             "Usable fraction of CPU capacity.")
    d.define("disk.capacity.threshold", T.DOUBLE, 0.8, Range.between(0, 1), I.MEDIUM,
             "Usable fraction of disk capacity.")
    d.define("network.inbound.capacity.threshold", T.DOUBLE, 0.8, Range.between(0, 1), I.MEDIUM,
             "Usable fraction of NW-in capacity.")
    d.define("network.outbound.capacity.threshold", T.DOUBLE, 0.8, Range.between(0, 1), I.MEDIUM,
             "Usable fraction of NW-out capacity.")
    d.define("cpu.low.utilization.threshold", T.DOUBLE, 0.0, Range.between(0, 1), I.LOW,
             "Below this avg utilization the resource is considered low-utilized.")
    d.define("disk.low.utilization.threshold", T.DOUBLE, 0.0, Range.between(0, 1), I.LOW, "")
    d.define("network.inbound.low.utilization.threshold", T.DOUBLE, 0.0, Range.between(0, 1), I.LOW, "")
    d.define("network.outbound.low.utilization.threshold", T.DOUBLE, 0.0, Range.between(0, 1), I.LOW, "")
    d.define("max.replicas.per.broker", T.LONG, 10_000, Range.at_least(1), I.MEDIUM,
             "ReplicaCapacityGoal ceiling.")
    d.define("proposal.expiration.ms", T.LONG, 60_000, Range.at_least(0), I.MEDIUM,
             "Precomputed proposal freshness budget.")
    d.define("num.proposal.precompute.threads", T.INT, 1, Range.at_least(1), I.LOW,
             "Precompute workers (host-side; device search is batched).")
    d.define("max.solver.rounds", T.INT, 2000, Range.at_least(1), I.MEDIUM,
             "TPU solver: max accepted-move rounds per goal.")
    d.define("solver.candidates.per.round", T.INT, 4096, Range.at_least(16), I.MEDIUM,
             "TPU solver: candidate actions scored per round.")
    d.define("solver.moves.per.round", T.INT, 64, Range.at_least(1), I.MEDIUM,
             "TPU solver: max non-conflicting moves applied per round.")
    d.define("concurrency.adjuster.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Re-tune execution concurrency caps each interval from broker "
             "health and (At/Under)MinISR state (Executor.java:465-683).")
    d.define("concurrency.adjuster.interval.ms", T.LONG, 1_000,
             Range.at_least(1), I.LOW,
             "ConcurrencyAdjuster evaluation interval.")
    d.define("concurrency.adjuster.min.isr.check.enabled", T.BOOLEAN, False,
             None, I.LOW, "Consult (At/Under)MinISR state when adjusting "
             "(reference default: false, ExecutorConfig.java:583).")
    d.define("concurrency.adjuster.min.isr.retention.ms", T.LONG, 30_000,
             Range.at_least(1), I.LOW,
             "TopicMinIsrCache entry TTL (TopicMinIsrCache.java).")
    d.define("concurrency.adjuster.min.isr.cache.size", T.INT, 10_000,
             Range.at_least(1), I.LOW, "TopicMinIsrCache size bound.")
    d.define("concurrency.adjuster.inter.broker.replica.enabled", T.BOOLEAN,
             True, None, I.LOW, "Adjust inter-broker movement caps.")
    d.define("concurrency.adjuster.leadership.enabled", T.BOOLEAN, True, None,
             I.LOW, "Adjust leadership movement caps.")
    d.define("concurrency.adjuster.max.leadership.movements", T.INT, 1_100,
             Range.at_least(1), I.LOW, "Adjuster ceiling for cluster "
             "leadership movements (ExecutorConfig.java:350).")
    d.define("concurrency.adjuster.min.leadership.movements", T.INT, 100,
             Range.at_least(1), I.LOW, "Adjuster floor for leadership.")
    # AIMD tuning surface (ExecutorConfig.java:340-583).
    d.define("concurrency.adjuster.additive.increase.inter.broker.replica",
             T.INT, 1, Range.at_least(1), I.LOW,
             "Per-tick additive increase of the per-broker inter-broker "
             "movement cap while the cluster is healthy.")
    d.define("concurrency.adjuster.additive.increase.leadership", T.INT, 100,
             Range.at_least(1), I.LOW,
             "Per-tick additive increase of the cluster leadership cap.")
    d.define("concurrency.adjuster.additive.increase.leadership.per.broker",
             T.INT, 25, Range.at_least(1), I.LOW,
             "Per-tick additive increase of the per-broker leadership cap.")
    d.define("concurrency.adjuster.multiplicative.decrease.inter.broker.replica",
             T.DOUBLE, 2.0, Range.at_least(1), I.LOW,
             "Divisor applied to the inter-broker cap under min-ISR or "
             "metric-limit pressure.")
    d.define("concurrency.adjuster.multiplicative.decrease.leadership",
             T.DOUBLE, 2.0, Range.at_least(1), I.LOW,
             "Divisor applied to the cluster leadership cap under pressure.")
    d.define("concurrency.adjuster.multiplicative.decrease.leadership.per.broker",
             T.DOUBLE, 2.0, Range.at_least(1), I.LOW,
             "Divisor applied to the per-broker leadership cap under "
             "pressure.")
    d.define("concurrency.adjuster.min.partition.movements.per.broker", T.INT,
             1, Range.at_least(1), I.LOW,
             "Adjuster floor for per-broker inter-broker movements.")
    d.define("concurrency.adjuster.max.partition.movements.per.broker", T.INT,
             12, Range.at_least(1), I.LOW,
             "Adjuster ceiling for per-broker inter-broker movements.")
    d.define("concurrency.adjuster.min.leadership.movements.per.broker",
             T.INT, 25, Range.at_least(1), I.LOW,
             "Adjuster floor for per-broker leadership movements.")
    d.define("concurrency.adjuster.max.leadership.movements.per.broker",
             T.INT, 500, Range.at_least(1), I.LOW,
             "Adjuster ceiling for per-broker leadership movements.")
    d.define("concurrency.adjuster.leadership.per.broker.enabled", T.BOOLEAN,
             False, None, I.LOW,
             "Adjust the per-broker leadership cap too.")
    d.define("concurrency.adjuster.limit.log.flush.time.ms", T.DOUBLE, 2000.0,
             Range.at_least(0), I.LOW,
             "Broker log-flush p999 above this counts as a metric-limit "
             "violation.")
    d.define("concurrency.adjuster.limit.follower.fetch.local.time.ms",
             T.DOUBLE, 500.0, Range.at_least(0), I.LOW,
             "Follower-fetch local-time p999 limit.")
    d.define("concurrency.adjuster.limit.produce.local.time.ms", T.DOUBLE,
             1000.0, Range.at_least(0), I.LOW,
             "Produce local-time p999 limit.")
    d.define("concurrency.adjuster.limit.consumer.fetch.local.time.ms",
             T.DOUBLE, 500.0, Range.at_least(0), I.LOW,
             "Consumer-fetch local-time p999 limit.")
    d.define("concurrency.adjuster.limit.request.queue.size", T.DOUBLE,
             1000.0, Range.at_least(0), I.LOW,
             "Request-queue size limit.")
    d.define("min.num.brokers.violate.metric.limit.to.decrease.cluster.concurrency",
             T.INT, 2, Range.at_least(1), I.LOW,
             "Brokers that must exceed a metric limit before the adjuster "
             "decreases concurrency.")
    d.define("concurrency.adjuster.num.min.isr.check", T.INT, 5,
             Range.at_least(1), I.LOW,
             "Recent adjuster ticks whose (At/Under)MinISR observations "
             "stay sticky: pressure seen in ANY of the last N checks keeps "
             "the decrease signal active.")
    d.define("num.concurrent.leader.movements.per.broker", T.INT, 250,
             Range.at_least(1), I.MEDIUM,
             "Per-broker bound on leadership movements per batch.")
    d.define("min.execution.progress.check.interval.ms", T.LONG, 5_000,
             Range.at_least(1), I.LOW,
             "Floor for the progress-check interval override.")
    d.define("auto.stop.external.agent", T.BOOLEAN, True, None, I.MEDIUM,
             "Cancel reassignments started by an external tool before "
             "executing (maybeStopExternalAgent:1261).")
    d.define("list.partition.reassignment.timeout.ms", T.LONG, 60_000,
             Range.at_least(1), I.LOW, "listPartitionReassignments timeout.")
    d.define("list.partition.reassignment.max.attempts", T.INT, 3,
             Range.at_least(1), I.LOW, "listPartitionReassignments retries.")
    d.define("logdir.response.timeout.ms", T.LONG, 10_000, Range.at_least(1),
             I.LOW, "DescribeLogDirs per-broker timeout.")
    d.define("admin.client.request.timeout.ms", T.LONG, 30_000,
             Range.at_least(1), I.LOW, "AdminClient request timeout.")
    d.define("executor.notifier.class", T.CLASS,
             "cruise_control_tpu.executor.notifier.LoggingExecutorNotifier",
             None, I.LOW, "ExecutorNotifier implementation.")
    d.define("demotion.history.retention.time.ms", T.LONG, 86_400_000,
             Range.at_least(1), I.LOW,
             "How long recently-demoted brokers stay excluded.")
    d.define("removal.history.retention.time.ms", T.LONG, 86_400_000,
             Range.at_least(1), I.LOW,
             "How long recently-removed brokers stay excluded.")
    d.define("slow.task.alerting.backoff.ms", T.LONG, 60_000,
             Range.at_least(0), I.LOW,
             "Backoff between slow-task alerts.")
    d.define("solver.chain.fused", T.BOOLEAN, True, None, I.MEDIUM,
             "TPU solver: run the whole goal chain in one device dispatch "
             "(chain.chain_optimize_full) instead of one dispatch per goal "
             "phase.")
    d.define("solver.fused.chain.max.brokers", T.INT, 512, Range.at_least(0),
             I.MEDIUM,
             "Above this broker count the solver switches from the whole-"
             "chain single dispatch to bounded per-goal dispatches, so no "
             "single XLA execution runs for tens of seconds (whether a "
             "locally attached chip needs the bound is not measured on "
             "the current machine). 0 = never switch.")
    d.define("solver.dispatch.max.rounds", T.INT, 16, Range.at_least(1),
             I.MEDIUM,
             "Initial (and minimum) search rounds per device dispatch on "
             "the bounded per-goal path (the host loops to the same fixed "
             "point).")
    d.define("solver.wide.batch.min.brokers", T.INT, 512, Range.at_least(0),
             I.LOW,
             "Cluster size from which goals flagged prefers_wide_batches "
             "run with the widened source grid on the bounded per-goal "
             "path (0 disables wide batches entirely).")
    d.define("solver.wide.batch.source.multiplier", T.INT, 8,
             Range.at_least(1), I.LOW,
             "Source-grid width multiplier for prefers_wide_batches goals "
             "(sources capped at 2048, moves at 2x). Source-limited "
             "late-chain goals convert extra width directly into fewer "
             "rounds (measured at 7k/1M: x8 cuts total rounds 4,258 -> "
             "3,065 at identical balancedness and violated-goal set); "
             "validate quality at scale before raising further.")
    d.define("solver.partition.bucket.size", T.INT, 1024, Range.at_least(0),
             I.LOW,
             "Pad the model's partition axis up to a multiple of this so "
             "ordinary partition-count changes reuse the already-compiled "
             "solver kernels (XLA compiles per shape; a full-chain compile "
             "at large scale is minutes). 0 disables padding.")
    d.define("solver.broker.bucket.size", T.INT, 32, Range.at_least(0), I.LOW,
             "Pad the broker axis up to a multiple of this (see "
             "solver.partition.bucket.size). Pad brokers are masked out "
             "(broker_mask) and DEAD. 0 disables padding.")
    d.define("solver.dispatch.target.seconds", T.DOUBLE, 2.5,
             Range.at_least(0), I.MEDIUM,
             "Adaptive bounded-dispatch sizing: grow the per-dispatch round "
             "budget while a full dispatch completes under half this "
             "wall-clock, shrink when it overshoots 2x. Amortizes the "
             "fixed per-dispatch host cost (enqueue plus scalar readback; "
             "not measured on the current machine) while every dispatch "
             "stays bounded. 0 disables adaptation.")
    d.define("solver.megastep.donate", T.BOOLEAN, True, None, I.LOW,
             "Bounded megastep dispatches donate the mutable state tensors "
             "(assignment, leader_slot) to XLA so each dispatch rewrites "
             "them in place instead of allocating a fresh generation. "
             "Automatically disabled on zero-copy backends (CPU), where "
             "device arrays may alias host buffers owned by the "
             "incremental model pipeline.")
    d.define("solver.dispatch.async.readback", T.BOOLEAN, True, None, I.LOW,
             "Bounded-dispatch pipelining: enqueue the next megastep "
             "before reading the previous one's stats scalars, so the "
             "host-device readback RTT overlaps device compute. The "
             "adaptive dispatch controller then learns from the completed "
             "dispatch one step behind. Trajectory-invariant; the only "
             "cost is one speculative dispatch per pass, which runs no "
             "round.")
    d.define("solver.deficit.moves.cap", T.INT, 2048, Range.at_least(0),
             I.LOW,
             "Deficit-aware batch sizing for count-distribution goals on "
             "the bounded path: moves-per-round / source width are sized "
             "from the goal's measured total band violation (~2x the "
             "moves still needed), rounded up to a power of two and "
             "capped here, instead of the fixed configured width — an "
             "O(10k)-move imbalance stops burning hundreds of fixed-"
             "width rounds. Applies at/above "
             "solver.wide.batch.min.brokers; 0 disables sizing.")
    d.define("solver.direct.assignment.enabled", T.BOOLEAN, False, None,
             I.MEDIUM,
             "Direct-assignment transport kernels for the count-"
             "distribution goals (analyzer.direct): compute the per-"
             "broker / per-topic target counts on device and solve the "
             "surplus-to-deficit matching as a vectorized rank "
             "assignment in one (or a few) dispatches, instead of "
             "hundreds of acceptance-density-limited greedy rounds; the "
             "greedy rounds then only polish the feasibility-vetoed "
             "residue. Applies at/above solver.wide.batch.min.brokers "
             "(it replaces deficit-sized greedy; below the gate the "
             "greedy path is kept byte-identical) and only to chains "
             "whose prior goals the transport feasibility masks can "
             "represent. Ships OFF: enable only with the bench "
             "regression sentry green on the full fixture matrix — "
             "final quality is chaotically sensitive to source "
             "composition (two prior density fixes silently flipped the "
             "86.0 -> 82.74 CpuUsageDistribution canary).")
    d.define("solver.direct.max.sweeps", T.INT, 16, Range.at_least(1), I.LOW,
             "Sweep budget of one direct-assignment dispatch: each sweep "
             "re-plans the transport on the updated counts (vetoed "
             "pairings rotate to different destinations), so a bounded "
             "number of sweeps clears what feasibility allows and the "
             "rest falls to the greedy polish. The loop exits early when "
             "no movers remain OR a few consecutive sweeps apply nothing "
             "(a stalled rotation), so budget beyond convergence is "
             "near-free.")
    d.define("solver.direct.sparse.margin.frac", T.DOUBLE, 0.25,
             Range.between(0.0, 0.5), I.LOW,
             "Fractional band-edge margin of the sparse-aware transport "
             "plan (round 21): shed targets sit margin.frac x band-width "
             "inside the upper edge, fill targets the mirror above the "
             "lower (never below half a count, so 1-count bands keep a "
             "center-ward pull), and deterministic randomized rounding "
             "resolves the fractional per-cell targets so EXPECTED "
             "counts equal the fractional band math in every density "
             "regime. 0 reproduces the parked-at-the-edge plans that "
             "stalled the greedy polish; 0.5 pulls everything to the "
             "band center.")
    d.define("solver.direct.sparse.rounding.salt", T.STRING, "", None, I.LOW,
             "Extra salt folded (crc32, trace time) into the sparse "
             "plan's deterministic rounding seed. Empty keeps the "
             "module's fixed crc32 seed — byte-identical replays per "
             "configuration (the CCSA004 contract); fleets set distinct "
             "salts to decorrelate rounding across replicas without "
             "giving up determinism within each.")
    d.define("solver.direct.density.sparse.threshold", T.DOUBLE, 2.0,
             Range.at_least(0.0), I.LOW,
             "Per-goal density-aware path choice (round 23, ROADMAP 2d): "
             "below this many replicas per (topic, broker) transport "
             "cell, only the goals measured faster under direct at "
             "sparse geometry (TopicReplicaDistribution) keep the "
             "direct-transport arm; Replica/LeaderReplica take "
             "deficit-sized greedy there (the documented honest "
             "negative). At or above the threshold every direct-eligible "
             "goal keeps the direct arm. 0 disables the choice.")
    d.define("solver.fingerprint.skip.enabled", T.BOOLEAN, True, None, I.LOW,
             "Always-hot solver (round 18): snapshot EVERY goal's entry "
             "violation in ONE batched stats program before the bounded "
             "chain loop, and skip a goal's move/swap (and per-goal "
             "stats) dispatches entirely while the snapshot is valid and "
             "shows nothing to do — byte-identical to the unskipped "
             "path, since a violation-free goal applies nothing. Under "
             "sustained drift with warm starts most goals skip, so the "
             "per-goal dispatch floor collapses to one program.")
    d.define("solver.warm.start.enabled", T.BOOLEAN, False, None, I.MEDIUM,
             "Always-hot solver (round 18): seed each default-chain solve "
             "from the facade's last ACCEPTED (assignment, leader_slot) "
             "instead of the cold model state — proposals still diff "
             "against the TRUE current model, and a warm-seeded result "
             "worse than the cold path's sentry band (see "
             "solver.warm.start.quality.band) triggers a counted cold "
             "re-solve, so warm starts can never silently degrade "
             "proposals. OFF by default: warm-seeded searches may reach "
             "a different (quality-band-equivalent) optimum than cold "
             "ones, which flips byte-pinned replay digests.")
    d.define("solver.warm.start.quality.band", T.DOUBLE, 0.05,
             Range.at_least(0.0), I.LOW,
             "Warm-start fallback band: a warm-seeded solve whose "
             "balancedness_after drops more than this below the seed's "
             "own accepted balancedness, or that violates a goal the "
             "seed's solve did not, is discarded and re-solved cold "
             "(counted in solver_warm_fallbacks). Matches the bench "
             "regression sentry's balancedness canary band.")
    d.define("solver.compile.cache.enabled", T.BOOLEAN, True, None, I.LOW,
             "Persist XLA compilation artifacts across process restarts "
             "(the enable_persistent_compile_cache seam, called from "
             "facade start_up so SERVING processes get the cache without "
             "wrapper scripts). See solver.compile.cache.dir for where "
             "it lives.")
    d.define("solver.compile.cache.dir", T.STRING, None, None, I.LOW,
             "Directory of the persistent compile cache. Ignored when "
             "$JAX_COMPILATION_CACHE_DIR is set (jax then uses that "
             "directory and the program sets none in code); unset falls "
             "back to <checkout>/.jax_cache.")
    d.define("solver.compile.cache.min.compile.secs", T.DOUBLE, 1.0,
             Range.at_least(0.0), I.LOW,
             "Minimum backend-compile duration for an artifact to be "
             "persisted (jax_persistent_cache_min_compile_time_secs): "
             "keeps the cache to the expensive solver programs.")
    d.define("solver.prewarm.enabled", T.BOOLEAN, False, None, I.MEDIUM,
             "Always-hot solver (round 18): record every solved padded "
             "bucket-shape signature in the persistent compile cache "
             "directory, and have a fresh process compile "
             "the whole known-shape kernel set in a background thread at "
             "start_up (GoalOptimizer.prewarm_shape on inert synthetic "
             "models) — a new replica serves its first rebalance in "
             "seconds instead of paying the warmup compile on the "
             "request path. Requires solver.compile.cache.enabled; "
             "progress on GET /state and /fleet, compiles watched by "
             "xla_compile_cache_{hits,misses}.")
    d.define("solver.warm.start.precheck.enabled", T.BOOLEAN, True, None,
             I.LOW,
             "Warm-band pre-check (round 19, ROADMAP 3a tail): before "
             "committing to a full warm chain, score the seed against "
             "the CURRENT loads in one batched goal-stats program and "
             "skip the warm attempt when the seed's entry picture "
             "already breaches the sentry band (a violated goal its "
             "accepted solve did not have) — the measured drift case "
             "where warm pays attempt+fallback for the cold answer. "
             "Skips counted in solver_warm_precheck_skips. The skip "
             "path serves exactly the fallback's cold solve; a "
             "band-worse seed the full chain COULD have repaired back "
             "into the band is served cold instead — a forfeited warm "
             "win, never degraded quality.")
    d.define("forecast.enabled", T.BOOLEAN, False, None, I.MEDIUM,
             "Predictive rebalancing (round 19): fit a seasonal-trend "
             "forecaster over the monitor's windowed per-partition "
             "history in ONE batched jitted program, project each "
             "resource load forecast.horizon.windows ahead, and let the "
             "PredictiveViolationDetector raise PREDICTED_GOAL_VIOLATION "
             "anomalies whose fix PRECOMPUTES the proposal (never "
             "executes; see anomaly.detection.predictive.fix.enabled). "
             "OFF by default: off means off — the engine and detector "
             "cost one config read per tick and serving behavior is "
             "byte-identical (forecast_noop_overhead guards it).")
    d.define("forecast.fit.windows", T.INT, 16, Range.at_least(4), I.LOW,
             "Exactly how many of the monitor's most recent stable "
             "windows the forecaster fits (fixed so ONE program "
             "compiles per shape instead of one per history length); "
             "fewer available windows = forecast not ready "
             "(forecast_skipped_not_ready).")
    d.define("forecast.horizon.windows", T.INT, 6, Range.at_least(1), I.LOW,
             "How many windows past the last observation the forecaster "
             "projects. The violation-scoring view takes the per-cell "
             "PEAK over the horizon, so one goal-stats program answers "
             "'does any window within the horizon violate?'.")
    d.define("forecast.seasonal.period.windows", T.INT, 0,
             Range.at_least(0), I.LOW,
             "Seasonal period (windows) added to the fit basis as a "
             "sin/cos pair — set to the diurnal period in window units "
             "for daily load shapes; 0 = trend-only fit.")
    d.define("forecast.confidence.z", T.DOUBLE, 2.0, Range.at_least(0.0),
             I.LOW,
             "Confidence-band width in residual-RMS units reported with "
             "each projection (GET /forecast bandMax; detection scores "
             "the mean projection — documented in DESIGN.md).")
    d.define("anomaly.detection.predictive.fix.enabled", T.BOOLEAN, False,
             None, I.MEDIUM,
             "Opt-in PROACTIVE execution for predicted violations: when "
             "true, a PREDICTED_GOAL_VIOLATION fix runs a real "
             "self-healing rebalance BEFORE the violation materializes. "
             "Default false: the fix only precomputes (projected-model "
             "dry-run solve + warm-seed store + fleet pacer promotion) "
             "so the proposal is hot when the real violation lands.")
    d.define("self.healing.predicted.violation.enabled", T.BOOLEAN, True,
             None, I.LOW,
             "Per-type self-healing switch for PREDICTED_GOAL_VIOLATION "
             "anomalies (the notifier's FIX verdict gate). The fix is a "
             "dry-run precompute unless "
             "anomaly.detection.predictive.fix.enabled is also true, so "
             "the default-on only spends solver time, never moves.")
    d.define("futures.live.seed.enabled", T.BOOLEAN, True, None, I.LOW,
             "Futures engine (ROADMAP 5b tail): seed COMPARE_FUTURES "
             "twins from the LIVE cluster's geometry (brokers, racks, "
             "topics, RF) instead of the synthetic BASE_SPEC, and let "
             "the forecast_horizon template solve the REAL projected "
             "loads — candidate futures become futures of THIS cluster. "
             "Falls back to BASE_SPEC when the model is not ready.")
    d.define("fleet.bucket.broker.base", T.INT, 4, Range.at_least(1), I.LOW,
             "Fleet federation: smallest broker-axis bucket of the shared "
             "geometric shape grid (fleet.bucketing.BucketGrid). Every "
             "registered cluster's model is padded up to a grid point so "
             "N clusters share a handful of compiled chain kernels.")
    d.define("fleet.bucket.partition.base", T.INT, 256, Range.at_least(1),
             I.LOW,
             "Fleet federation: smallest partition-axis bucket of the "
             "shared geometric shape grid.")
    d.define("fleet.bucket.topic.base", T.INT, 8, Range.at_least(1), I.LOW,
             "Fleet federation: smallest bucket for the topic-count "
             "static solver argument (the [T, B] topic planes); pad "
             "topics host no replicas and are goal-neutral.")
    d.define("fleet.bucket.geometric.factor", T.DOUBLE, 2.0,
             Range.at_least(1.01), I.LOW,
             "Fleet federation: growth factor between grid points on both "
             "axes (bucket sizes base x factor^k; 2.0 = powers of two, "
             "bounding pad overhead below one octave).")
    d.define("fleet.precompute.cadence.ms", T.LONG, 60_000,
             Range.at_least(1), I.LOW,
             "Fleet federation: per-cluster proposal-precompute cadence "
             "enforced by the FleetScheduler's pacer (overridable per "
             "cluster via its registration overlay). The fleet analogue "
             "of the facade's own precompute loop.")
    d.define("fleet.scheduler.starvation.bound.ms", T.LONG, 30_000,
             Range.at_least(1), I.LOW,
             "Fleet federation: any queued solver job older than this "
             "runs next regardless of priority class, so one cluster's "
             "flood can delay but never starve another cluster's work. "
             "With megabatch coalescing the bound applies to BATCHES: "
             "the overdue job is picked first and its compatible queued "
             "peers ride along in its batch.")
    d.define("fleet.megabatch.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Megabatch fleet solver (round 14): the scheduler drains "
             "compatible queued precomputes (same bucket shape + goal "
             "chain) into ONE batched device program — same-bucket "
             "clusters stacked along a cluster axis and solved through "
             "the donated megastep kernels, byte-identical per cluster "
             "to serial solves. Solver throughput then scales with the "
             "batch, not threads. Disabled, every job runs solo (the "
             "round-6 behavior).")
    d.define("fleet.megabatch.width", T.INT, 4, Range.at_least(1), I.LOW,
             "Cluster-axis width of a megabatch program. FIXED per "
             "bucket shape: partially-filled batches pad with inert "
             "zero-weight cluster slots, so one compiled program per "
             "bucket shape serves any occupancy (occupancy is traced, "
             "never a new compile). More queued compatibles than the "
             "width split into multiple batches.")
    d.define("fleet.shard.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Device-sharded megabatch (round 23): with a device mesh "
             "attached, shard the megabatch CLUSTER axis across it — "
             "batch_width / n_devices cluster slots per device, each "
             "device early-exiting on its own shard's convergence, "
             "per-cluster results byte-identical to the single-device "
             "megabatch. Disabled (or single-device), batched solves "
             "run on one device as in round 14.")
    d.define("fleet.shard.workers", T.INT, 1, Range.at_least(1), I.MEDIUM,
             "Multi-replica control plane (round 23): number of fleet "
             "solver worker threads sharing the scheduler queue, the "
             "persistent AOT cache, and the shape registry. Placement "
             "is bucket-affine (a batch key sticks to the worker that "
             "first solved it, keeping its compiled programs hot) with "
             "work-stealing: overdue jobs (past the starvation bound) "
             "and idle workers steal across affinity, so the starvation "
             "bound holds fleet-wide. 1 = the single-worker round-6..22 "
             "behavior, byte-identical.")
    d.define("serving.task.queue.viewer.capacity", T.INT, 64,
             Range.at_least(1), I.LOW,
             "Serving front door (round 20): bound on QUEUED "
             "VIEWER-class async tasks (cheap reads: load, "
             "partition_load, ...). A full queue sheds the request with "
             "429 + Retry-After before any task is created.")
    d.define("serving.task.queue.solver.capacity", T.INT, 32,
             Range.at_least(1), I.LOW,
             "Serving front door: bound on QUEUED SOLVER-class async "
             "tasks (proposals, rebalance, broker ops, futures — the "
             "device-heavy endpoints).")
    d.define("serving.task.viewer.threads", T.INT, 4, Range.at_least(1),
             I.LOW,
             "Serving front door: worker threads draining the VIEWER "
             "task queue.")
    d.define("serving.task.solver.threads", T.INT, 2, Range.at_least(1),
             I.LOW,
             "Serving front door: worker threads draining the SOLVER "
             "task queue. These threads only WAIT on fleet-scheduler "
             "futures — the device work itself runs on the scheduler's "
             "worker, so this bounds concurrent waiters, not compiles.")
    d.define("serving.cache.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Serving front door: model-generation-keyed response cache. "
             "A response is identified by (cluster, endpoint, canonical "
             "params, load-model generation, goal-chain fingerprint) and "
             "served byte-identical until the generation or the "
             "configured goal chain moves. Only deterministic "
             "generation-pure endpoints (proposals, futures) are "
             "cacheable; cache-busting params (ignore_proposal_cache, "
             "data_from, what_if, ...) bypass it.")
    d.define("serving.cache.max.entries", T.INT, 256, Range.at_least(1),
             I.LOW,
             "Serving front door: response-cache entry bound (oldest "
             "evicted first; entries also die with their generation).")
    d.define("serving.cache.state.enabled", T.BOOLEAN, False, None, I.LOW,
             "Serving front door: also cache GET /state envelopes. OFF "
             "by default — executor progress and anomaly-detector state "
             "move WITHOUT a model-generation bump, so a generation-"
             "keyed /state cache can serve stale operational truth; "
             "enable only for dashboards that poll faster than they "
             "need freshness.")
    d.define("serving.coalesce.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Serving front door: cross-user request coalescing. "
             "Identical concurrent in-flight requests (same cluster, "
             "endpoint, canonical params, generation, goal chain) "
             "attach to ONE solve — each caller still gets its own "
             "session-bound User-Task-ID, but every task shares the "
             "leader's future (the round-15 precompute-coalescing "
             "contract generalized to user traffic).")
    d.define("serving.admission.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Serving front door: queue-depth-aware admission control "
             "layered ABOVE the per-cluster breaker. New work arriving "
             "while a class queue is past its depth bound is shed with "
             "429 + Retry-After derived from the observed per-class "
             "service rate (depth x EWMA service time). Polls of "
             "existing tasks, cache hits and coalesced joins are never "
             "shed.")
    d.define("serving.admission.queue.viewer.max", T.INT, 32,
             Range.at_least(1), I.LOW,
             "Serving front door: VIEWER queue depth beyond which new "
             "viewer requests are shed (must not exceed the queue "
             "capacity or the capacity bound sheds first).")
    d.define("serving.admission.queue.solver.max", T.INT, 8,
             Range.at_least(0), I.LOW,
             "Serving front door: SOLVER queue depth beyond which new "
             "solver requests are shed. 0 sheds ALL new solver work — a "
             "drain valve for maintenance windows.")
    d.define("tracing.enabled", T.BOOLEAN, True, None, I.LOW,
             "Pipeline span tracing (utils.tracing): every operation — "
             "sampling, model build, per-goal solve, execution — records "
             "a span tree served at GET /trace, with per-stage latency "
             "histograms on /metrics. Disabled, the tracer is a shared "
             "no-op context manager: nothing on the solver hot path.")
    d.define("tracing.max.traces", T.INT, 256, Range.at_least(1), I.LOW,
             "Bound on the in-memory ring of recent traces (oldest "
             "evicted; ~a few KB per trace).")
    d.define("tracing.jsonl.path", T.STRING, "", None, I.LOW,
             "Append one JSON line per completed trace to this file "
             "(bench/CI artifact hook); empty = off.")
    d.define("tracing.jsonl.max.bytes", T.LONG, 67_108_864,
             Range.at_least(0), I.LOW,
             "Size cap on the tracing JSONL dump: when an append would "
             "push the file past this, it is rotated to <path>.1 (see "
             "tracing.jsonl.max.files for how many rotated generations "
             "are kept) so a long-running process can never grow the "
             "dump without bound. 0 = unlimited.")
    d.define("tracing.jsonl.max.files", T.INT, 1, Range.at_least(1), I.LOW,
             "Rotated JSONL generations kept: rotation cascades "
             "<path>.1 -> <path>.2 -> ... up to this count before the "
             "oldest falls off. 1 preserves the historical single-"
             "generation behavior.")
    d.define("solver.flight.recorder.enabled", T.BOOLEAN, True, None, I.LOW,
             "Solver flight recorder (utils.flight_recorder): per-goal, "
             "per-dispatch search telemetry — acceptance density, "
             "candidate-kill attribution, per-round violation "
             "trajectories, deficit-sizing decisions, AdaptiveDispatch "
             "state — served at GET /solver and exported as "
             "solver_flight_* sensors. Recording never changes solver "
             "trajectories (byte-parity pinned in tests); disabled, "
             "every hook is a shared no-op (bench-guarded by "
             "flight_recorder_noop_overhead).")
    d.define("solver.flight.recorder.max.passes", T.INT, 64,
             Range.at_least(1), I.LOW,
             "Bound on the in-memory ring of recorded optimization "
             "passes (oldest evicted).")
    d.define("solver.flight.recorder.ring.rounds", T.INT, 128,
             Range.at_least(0), I.LOW,
             "Length of the on-device per-round stats ring carried "
             "through the single-device move megasteps (~24 bytes per "
             "slot; older rounds of a longer dispatch are overwritten "
             "oldest-first). Trace-time constant: changing it recompiles "
             "the recording chain kernels. 0 records at dispatch "
             "granularity only.")
    d.define("heal.ledger.enabled", T.BOOLEAN, True, None, I.LOW,
             "Heal ledger (utils.heal_ledger): per-anomaly lifecycle "
             "chains — detection, notifier verdicts, fix dispatch, "
             "model/solve phases (flight-recorder pass ids linked), "
             "execution progress, and the terminal outcome — served at "
             "GET /heals and exported as heal_phase_seconds{phase=} / "
             "time_to_heal_seconds{type=} histograms and the "
             "heals_open{type=} gauge. Observation only: proposals and "
             "final assignments are byte-identical with the ledger on "
             "or off (pinned); disabled, every hook is the shared NO_HEAL "
             "no-op (bench-guarded by heal_ledger_noop_overhead).")
    d.define("heal.ledger.max.chains", T.INT, 256, Range.at_least(1), I.LOW,
             "Bound on retained heal chains per facade (oldest evicted; "
             "a still-open evicted chain terminates as 'evicted' so no "
             "heal silently vanishes from the export).")
    d.define("heal.ledger.max.phases", T.INT, 64, Range.at_least(4), I.LOW,
             "Bound on phase transitions kept per chain; further "
             "transitions are counted in the chain's droppedPhases "
             "field instead of growing it without bound.")
    # --- Request journeys + SLO engine (round 18) ---
    d.define("journey.enabled", T.BOOLEAN, True, None, I.LOW,
             "Request journeys (serving.journey): per-request segment "
             "attribution — admission, cache lookup, coalesce join, "
             "queue wait, fleet-scheduler wait, model build, solve "
             "(flight-recorder pass ids + heal chain linked), proposal "
             "diff, render, cache store — kept in a bounded ring served "
             "at GET /journeys and exported as "
             "journey_segment_seconds{endpoint=,segment=} histograms. "
             "Observation only: responses are byte-identical with "
             "journeys on or off (pinned); disabled, the open() hook "
             "returns the shared NO_JOURNEY no-op (bench-guarded by "
             "journey_noop_overhead).")
    d.define("journey.max.entries", T.INT, 256, Range.at_least(1), I.LOW,
             "Bound on the in-memory ring of completed journeys per "
             "facade (oldest evicted; ~1 KB per journey).")
    d.define("slo.enabled", T.BOOLEAN, False, None, I.MEDIUM,
             "SLO engine (utils.slo): declarative objectives evaluated "
             "over sliding multi-window counters, exported as "
             "slo_error_budget_remaining{objective=} and "
             "slo_burn_rate{objective=,window=} and served at GET /slo. "
             "Off (default) the engine records nothing and every probe "
             "is ns-scale (bench-guarded by slo_noop_overhead).")
    d.define("slo.objectives", T.LIST, ["latency", "error", "shed"], None,
             I.LOW,
             "Active objective kinds (subset of latency, error, shed, "
             "staleness, heal); each kind reads its own "
             "slo.objectives.<kind>.* budget/threshold keys.")
    d.define("slo.objectives.latency.quantile", T.DOUBLE, 0.99,
             Range.between(0, 1), I.LOW,
             "Latency objective: the serving_request_seconds quantile "
             "the threshold applies to (reported on GET /slo; the burn "
             "accounting itself is per-request event-based).")
    d.define("slo.objectives.latency.threshold.seconds", T.DOUBLE, 2.0,
             Range.at_least(0), I.LOW,
             "Latency objective: a successful request slower than this "
             "is a bad event against the latency budget.")
    d.define("slo.objectives.latency.budget", T.DOUBLE, 0.05,
             Range.between(0, 1), I.LOW,
             "Latency objective: tolerated bad-event fraction (error "
             "budget). Burn rate = observed bad fraction / budget.")
    d.define("slo.objectives.error.budget", T.DOUBLE, 0.01,
             Range.between(0, 1), I.LOW,
             "Error objective: tolerated fraction of requests answering "
             "5xx/4xx (sheds excluded — they have their own objective).")
    d.define("slo.objectives.shed.budget", T.DOUBLE, 0.05,
             Range.between(0, 1), I.LOW,
             "Shed objective: tolerated fraction of requests answered "
             "429 by the admission layer.")
    d.define("slo.objectives.staleness.threshold.seconds", T.DOUBLE, 300.0,
             Range.at_least(0), I.LOW,
             "Staleness objective: a stale-serve whose proposal age "
             "exceeds this is a bad event.")
    d.define("slo.objectives.staleness.budget", T.DOUBLE, 0.05,
             Range.between(0, 1), I.LOW,
             "Staleness objective: tolerated bad-event fraction among "
             "stale serves.")
    d.define("slo.objectives.heal.threshold.seconds", T.DOUBLE, 600.0,
             Range.at_least(0), I.LOW,
             "Heal objective: a completed heal chain slower than this "
             "(detection -> cleared) is a bad event.")
    d.define("slo.objectives.heal.budget", T.DOUBLE, 0.1,
             Range.between(0, 1), I.LOW,
             "Heal objective: tolerated fraction of slow heals.")
    d.define("slo.burn.windows", T.LIST,
             ["300", "3600", "1800", "21600"], None, I.LOW,
             "Burn-rate windows in seconds, ordered fast-short, "
             "fast-long, slow-short, slow-long (the multi-window "
             "multi-burn-rate alerting shape: a page needs BOTH windows "
             "of a pair burning, so a blip can't page and a slow leak "
             "can't hide).")
    d.define("slo.burn.fast.threshold", T.DOUBLE, 14.4,
             Range.at_least(0), I.LOW,
             "Fast-pair burn multiple that raises SLO_BURN (14.4x "
             "spends 2% of a 30-day budget in an hour).")
    d.define("slo.burn.slow.threshold", T.DOUBLE, 6.0,
             Range.at_least(0), I.LOW,
             "Slow-pair burn multiple that raises SLO_BURN (6x spends "
             "5% of a 30-day budget in 6 hours).")
    d.define("profiling.enabled", T.BOOLEAN, True, None, I.LOW,
             "On-demand device profiling (GET /profile): "
             "jax.profiler.trace captures of live solves plus the "
             "in-process op-class microbench (utils.profiling; "
             "single-flight, busy requests get 503 + Retry-After).")
    d.define("profiling.trace.dir", T.STRING, "/tmp/cc_profile", None,
             I.LOW,
             "Directory receiving Perfetto/TensorBoard trace captures "
             "(one timestamped subdirectory per capture).")
    d.define("profiling.max.duration.seconds", T.DOUBLE, 60.0,
             Range.at_least(0.05), I.LOW,
             "Cap on one profile capture's duration_s: the capture holds "
             "the profiler gate and buffers host/device events for its "
             "whole window, so an oversized request is clamped, not "
             "honored.")
    d.define("xla.telemetry.enabled", T.BOOLEAN, True, None, I.LOW,
             "Hook jax.monitoring compile events (per padded-bucket-shape "
             "count + seconds — the recompile-churn watchdog), "
             "compilation-cache hit/miss counters, and device memory "
             "gauges into /metrics (utils.xla_telemetry).")
    # --- Resilience layer (utils/resilience.py, round 9) ---
    d.define("resilience.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Retry/backoff + circuit breaking on every external "
             "interaction (sampling fetch, admin calls, reassignment "
             "submission, fleet jobs, detector runs). Disabled, every "
             "wrapped call is a bare passthrough (ns-scale, bench-"
             "guarded by resilience_noop_overhead).")
    d.define("resilience.retry.max.attempts", T.INT, 5, Range.at_least(1),
             I.MEDIUM, "Attempts per wrapped call (1 = no retries).")
    d.define("resilience.retry.base.backoff.ms", T.LONG, 100,
             Range.at_least(0), I.LOW,
             "Backoff before the first re-attempt; doubles (see "
             "multiplier) up to the max per further attempt.")
    d.define("resilience.retry.max.backoff.ms", T.LONG, 10_000,
             Range.at_least(0), I.LOW, "Backoff ceiling per attempt.")
    d.define("resilience.retry.backoff.multiplier", T.DOUBLE, 2.0,
             Range.at_least(1), I.LOW, "Exponential backoff growth factor.")
    d.define("resilience.retry.jitter.ratio", T.DOUBLE, 0.2,
             Range.between(0, 1), I.LOW,
             "Fraction of the exponential backoff subtracted by the "
             "DETERMINISTIC seeded jitter (crc32 of seed:op:attempt — "
             "replayable, not a PRNG stream).")
    d.define("resilience.retry.seed", T.INT, 0, None, I.LOW,
             "Jitter seed; the same seed replays the same backoff "
             "schedule byte-for-byte (chaos-test determinism).")
    d.define("resilience.retry.overall.deadline.ms", T.LONG, 60_000,
             Range.at_least(1), I.LOW,
             "Overall wall budget per wrapped call: a retry whose "
             "backoff would overrun it gives up instead of sleeping.")
    d.define("resilience.breaker.failure.threshold", T.INT, 5,
             Range.at_least(0), I.MEDIUM,
             "Consecutive failures per target (cluster id, detector, "
             "model path) before its circuit breaker opens; 0 disables "
             "breaking while keeping retries.")
    d.define("resilience.breaker.recovery.ms", T.LONG, 30_000,
             Range.at_least(1), I.LOW,
             "Open-breaker recovery window; afterwards one half-open "
             "probe decides reopen vs. close. Also the Retry-After "
             "hint on 503 responses for open targets.")
    d.define("resilience.sampling.min.completeness", T.DOUBLE, 0.5,
             Range.between(0, 1), I.MEDIUM,
             "Minimum fraction of the partition universe a sampling "
             "interval must fetch to be ingested: windows above the "
             "floor are accepted PARTIAL (degraded beats absent), "
             "below it rejected (PartialWindowError).")
    d.define("resilience.executor.dead.letter.attempts", T.INT, 3,
             Range.at_least(1), I.MEDIUM,
             "Failed submissions per execution task before it is dead-"
             "lettered to the EXECUTION_ABANDONED terminal state (with "
             "a notifier event) instead of hanging the execution.")
    # --- Chaos harness (testing/chaos.py) ---
    d.define("chaos.enabled", T.BOOLEAN, False, None, I.LOW,
             "Wrap the admin backend in the deterministic fault "
             "injector (game-day drills; NEVER in production serving).")
    d.define("chaos.seed", T.INT, 0, None, I.LOW,
             "Fault-schedule seed: the same seed injects the same "
             "fault sequence byte-for-byte.")
    d.define("chaos.fault.rate", T.DOUBLE, 0.1, Range.between(0, 1), I.LOW,
             "Per-call injected fault probability (timeout / transient "
             "/ partial / slow, crc32-uniform).")
    d.define("chaos.broker.flap.rate", T.DOUBLE, 0.0, Range.between(0, 1),
             I.LOW,
             "Per-call probability that alive_brokers transiently "
             "omits one deterministic broker (flap injection; opt-in — "
             "flapped destinations DEAD-mark in-flight tasks).")
    # --- Digital-twin scenario harness (testing/simulator.py, round 11) ---
    d.define("scenario.tick.seconds", T.DOUBLE, 60.0, Range.at_least(0.001),
             I.LOW,
             "Simulated seconds each digital-twin tick advances the "
             "injected clock (the scenario harness's time step).")
    d.define("scenario.default.ticks", T.INT, 120, Range.at_least(1), I.LOW,
             "Default number of simulated ticks a scenario runs when the "
             "caller does not override it.")
    d.define("scenario.what.if.max.ticks", T.INT, 240, Range.at_least(1),
             I.LOW,
             "Cap on the tick count a PROPOSALS ?what_if= request may ask "
             "for (a what-if replay is real solver work; unbounded ticks "
             "would let one request monopolize the device).")
    d.define("scenario.slo.balancedness.min", T.DOUBLE, 75.0,
             Range.between(0, 100), I.LOW,
             "Quality SLO floor: a tick whose balancedness score sits "
             "below this (once detection has scored at all) counts as an "
             "SLO violation in the scenario report.")
    d.define("scenario.slo.heal.ticks", T.INT, 30, Range.at_least(1), I.LOW,
             "Stability SLO: an injected fault not healed within this "
             "many ticks — or never healed — is an SLO violation.")
    d.define("scenario.slo.moves.per.simhour", T.DOUBLE, 0.0,
             Range.at_least(0), I.LOW,
             "Churn SLO: replica moves per simulated hour above this "
             "rate are an SLO violation (0 disables the churn SLO).")
    d.define("scenario.proposal.probe.ticks", T.INT, 10, Range.at_least(0),
             I.LOW,
             "Every N simulated ticks the scenario harness issues a "
             "client-style proposals() probe so degraded serving "
             "(stale=true responses, model-build failures) is part of "
             "the scored trajectory (0 disables probing).")
    # --- Futures engine (futures/, round 15) ---
    d.define("futures.default.count", T.INT, 8, Range.at_least(1), I.LOW,
             "Candidate futures a COMPARE_FUTURES request evaluates when "
             "num_futures is not given (templates round-robin, seeds "
             "advance per cycle — every row replayable via "
             "what_if=random:<template>:<seed>).")
    d.define("futures.max.count", T.INT, 32, Range.at_least(1), I.LOW,
             "Cap on num_futures per COMPARE_FUTURES request: each "
             "future costs a twin advance (host) and a batched solve "
             "slot (device); unbounded requests would let one client "
             "monopolize both.")
    d.define("futures.default.ticks", T.INT, 12, Range.at_least(4), I.LOW,
             "Default advance horizon (simulated ticks to each future's "
             "decision point) when a COMPARE_FUTURES request omits "
             "ticks. Floor 4: the twin fills one metrics window per "
             "tick and the decision model build needs its windows.")
    d.define("futures.max.ticks", T.INT, 60, Range.at_least(4), I.LOW,
             "Cap on a COMPARE_FUTURES advance horizon (the advance is "
             "per-future host-side simulation; the what-if replay cap "
             "scenario.what.if.max.ticks plays the same role for full-"
             "loop replays).")
    d.define("futures.batch.width", T.INT, 8, Range.at_least(1), I.LOW,
             "Cluster-axis width of a batched futures solve (the "
             "evaluator's direct path; fleet-coalesced futures use "
             "fleet.megabatch.width). Fixed per bucket shape: partial "
             "chunks pad with inert slots so one compiled program per "
             "shape serves any occupancy.")
    # --- Red-team scenario mining (redteam/, round 22) ---
    d.define("redteam.enabled", T.BOOLEAN, True, None, I.LOW,
             "Serve the mined regression frontier (GET /redteam, "
             "what_if=mined:<id> replays). False = both surfaces answer "
             "400 and nothing else changes: mining only ever runs when "
             "explicitly invoked (bench.py --redteam), never on the "
             "serving path.")
    d.define("redteam.population", T.INT, 12, Range.at_least(2), I.LOW,
             "Candidates per mining generation (half mutations of the "
             "current frontier, half fresh crc32-derived samples; "
             "generation 0 is all fresh).")
    d.define("redteam.generations", T.INT, 4, Range.at_least(1), I.LOW,
             "Mining generations per sweep: sample -> megabatch screen "
             "-> full-loop score survivors -> keep the K worst -> "
             "mutate.")
    d.define("redteam.survivors", T.INT, 4, Range.at_least(1), I.LOW,
             "Worst-screened candidates per generation that earn a "
             "full-loop scored replay (detection + self-healing on) — "
             "the expensive half of the eval budget.")
    d.define("redteam.frontier.size", T.INT, 8, Range.at_least(1), I.LOW,
             "Worst-case survivors the frontier retains (lowest SLO "
             "margin first, ties broken on entry id byte-stably).")
    d.define("redteam.ticks", T.INT, 24, Range.at_least(4), I.LOW,
             "Full-loop horizon of a mined candidate (its sampled story "
             "compresses into this many ticks, faults included). Floor "
             "4: one metrics window fills per tick.")
    d.define("redteam.eval.budget", T.INT, 200, Range.at_least(1), I.LOW,
             "Total candidate evaluations (megabatch screens + full-"
             "loop replays) one sweep may spend; exhaustion ends the "
             "sweep partial=True with the reason recorded — never a "
             "silent cap.")
    d.define("redteam.frontier.path", T.STRING,
             "fileStore/redteam_frontier.json", None, I.LOW,
             "The committed regression frontier file GET /redteam and "
             "what_if=mined:<id> serve (sorted-keys JSON; every entry "
             "replayable byte-identically).")
    d.define("goal.violation.distribution.threshold.multiplier", T.DOUBLE, 1.0,
             Range.at_least(1), I.LOW,
             "Detector-triggered balance-threshold relaxation.")
    d.define("goal.balancedness.priority.weight", T.DOUBLE, 1.1, Range.at_least(1), I.LOW,
             "Geometric weight per goal-priority level in balancedness score.")
    d.define("goal.balancedness.strictness.weight", T.DOUBLE, 1.5, Range.at_least(1), I.LOW,
             "Extra weight for hard goals in balancedness score.")
    d.define("fast.mode.per.broker.move.timeout.ms", T.LONG, 500, Range.at_least(1), I.LOW,
             "Fast-mode (fast_mode=true request param) per-broker time "
             "budget: each goal's search wall-clock is capped at this "
             "value x num_brokers, and every goal runs the wide-batch "
             "grid (fewer, coarser rounds). Batch-search mapping of the "
             "reference's per-broker greedy timeout.")
    d.define("intra.broker.goals", T.LIST,
             ["IntraBrokerDiskCapacityGoal", "IntraBrokerDiskUsageDistributionGoal"],
             None, I.LOW, "Goal chain for rebalance_disk/remove_disks.")
    d.define("optimization.options.generator.class", T.CLASS, None, None,
             I.LOW,
             "Pluggable OptimizationOptions generation for goal-violation "
             "detection and cached-proposal computation "
             "(DefaultOptimizationOptionsGenerator.java).")
    d.define("rack.aware.goal.rack.id.mapper.class", T.CLASS, None, None,
             I.LOW,
             "Transforms broker rack ids before rack-aware goals group by "
             "them, e.g. collapsing AZ suffixes (goals/rackaware/"
             "RackAwareGoalRackIdMapper.java).")
    d.define("topics.excluded.from.partition.movement", T.STRING, "", None,
             I.MEDIUM, "Regex of topics never moved.")
    d.define("topic.replica.count.balance.min.gap", T.INT, 2,
             Range.at_least(0), I.LOW,
             "TopicReplicaDistribution band minimum width.")
    d.define("topic.replica.count.balance.max.gap", T.INT, 40,
             Range.at_least(0), I.LOW,
             "TopicReplicaDistribution band maximum width.")
    d.define("topics.with.min.leaders.per.broker", T.STRING, "", None, I.LOW,
             "Regex of topics MinTopicLeadersPerBrokerGoal applies to.")
    d.define("min.topic.leaders.per.broker", T.INT, 1, Range.at_least(0),
             I.LOW, "Leader floor per broker for matched topics.")
    d.define("allow.capacity.estimation.on.proposal.precompute", T.BOOLEAN,
             True, None, I.LOW,
             "Precompute passes may estimate missing capacities.")
    d.define("broker.set.resolver.class", T.CLASS, None, None, I.LOW,
             "BrokerSet membership resolver plugin.")
    d.define("broker.set.assignment.policy.class", T.CLASS, None, None, I.LOW,
             "BrokerSet assignment policy plugin.")
    d.define("broker.set.config.file", T.STRING, "config/brokerSets.json",
             None, I.LOW, "BrokerSet definitions.")
    d.define("overprovisioned.min.brokers", T.INT, 3, Range.at_least(1),
             I.LOW, "Provisioner floor before recommending removal.")
    d.define("overprovisioned.max.replicas.per.broker", T.LONG, 1_500,
             Range.at_least(1), I.LOW,
             "Replica ceiling that still counts as over-provisioned.")
    d.define("overprovisioned.min.extra.racks", T.INT, 2, Range.at_least(0),
             I.LOW, "Extra racks required to call a cluster over-provisioned.")
    d.define("metadata.factor.exponent", T.DOUBLE, 1.0, Range.at_least(0),
             I.LOW, "Metadata-scale exponent in provision recommendations.")

    # --- Executor (ExecutorConfig.java) ---
    d.define("num.concurrent.partition.movements.per.broker", T.INT, 10, Range.at_least(1),
             I.HIGH, "Per-broker inter-broker replica move cap.")
    d.define("max.num.cluster.partition.movements", T.INT, 1250, Range.at_least(1), I.HIGH,
             "Cluster-wide in-flight replica move cap.")
    d.define("num.concurrent.intra.broker.partition.movements", T.INT, 2, Range.at_least(1),
             I.MEDIUM, "Per-broker intra-broker (disk) move cap.")
    d.define("num.concurrent.leader.movements", T.INT, 1000, Range.at_least(1), I.HIGH,
             "Cluster-wide leadership movement cap.")
    d.define("max.num.cluster.movements", T.INT, 1250, Range.at_least(1), I.MEDIUM,
             "Upper bound of total in-flight movements.")
    d.define("execution.progress.check.interval.ms", T.LONG, 10_000, Range.at_least(1), I.HIGH,
             "Execution progress poll interval.")
    d.define("default.replication.throttle", T.LONG, None, None, I.MEDIUM,
             "Bytes/sec replication throttle during moves (None = no throttle).")
    d.define("replica.movement.strategies", T.LIST,
             ["cruise_control_tpu.executor.strategy.BaseReplicaMovementStrategy"],
             None, I.LOW, "Chain of replica movement orderings.")
    d.define("default.replica.movement.strategies", T.LIST,
             ["cruise_control_tpu.executor.strategy.BaseReplicaMovementStrategy"],
             None, I.LOW, "Default strategy chain.")
    d.define("executor.concurrency.adjuster.enabled", T.BOOLEAN, True, None, I.MEDIUM,
             "Adaptive concurrency adjuster on/off.")
    d.define("executor.concurrency.adjuster.interval.ms", T.LONG, 360_000, Range.at_least(1),
             I.LOW, "Concurrency adjuster cadence.")
    d.define("leader.movement.timeout.ms", T.LONG, 180_000, Range.at_least(1), I.LOW,
             "Leadership movement timeout before marking dead.")
    d.define("task.execution.alerting.threshold.ms", T.LONG, 90_000, Range.at_least(1), I.LOW,
             "Slow-task alert threshold.")
    d.define("admin.client.class", T.CLASS,
             "cruise_control_tpu.executor.admin.SimulatedAdminBackend",
             None, I.HIGH, "Cluster admin backend (simulated or Kafka).")

    # --- Anomaly detector (AnomalyDetectorConfig.java) ---
    d.define("anomaly.detection.interval.ms", T.LONG, 300_000, Range.at_least(1), I.HIGH,
             "Base detector cadence.")
    d.define("goal.violation.detection.interval.ms", T.LONG, None, None, I.LOW,
             "Override for goal-violation detector cadence.")
    d.define("metric.anomaly.detection.interval.ms", T.LONG, None, None, I.LOW, "")
    d.define("broker.failure.detection.backoff.ms", T.LONG, 300_000, Range.at_least(1), I.LOW, "")
    d.define("anomaly.notifier.class", T.CLASS,
             "cruise_control_tpu.detector.notifier.SelfHealingNotifier",
             None, I.HIGH, "AnomalyNotifier implementation.")
    d.define("self.healing.enabled", T.BOOLEAN, False, None, I.HIGH,
             "Global self-healing toggle.")
    d.define("self.healing.broker.failure.enabled", T.BOOLEAN, True, None, I.MEDIUM, "")
    d.define("self.healing.goal.violation.enabled", T.BOOLEAN, True, None, I.MEDIUM, "")
    d.define("self.healing.disk.failure.enabled", T.BOOLEAN, True, None, I.MEDIUM, "")
    d.define("self.healing.metric.anomaly.enabled", T.BOOLEAN, False, None, I.MEDIUM, "")
    d.define("self.healing.topic.anomaly.enabled", T.BOOLEAN, False, None, I.MEDIUM, "")
    d.define("self.healing.maintenance.event.enabled", T.BOOLEAN, False, None, I.MEDIUM, "")
    d.define("self.healing.slo.burn.enabled", T.BOOLEAN, False, None,
             I.MEDIUM,
             "Per-type self-healing switch for SLO_BURN anomalies (the "
             "notifier's FIX verdict gate). The fix is a mitigation "
             "nudge — it marks the predictive precompute pending so the "
             "next fleet cycle refreshes proposals — never a move.")
    d.define("maintenance.event.reader.class", T.CLASS,
             "cruise_control_tpu.detector.maintenance.InMemoryMaintenanceEventReader",
             None, I.MEDIUM,
             "Pluggable maintenance-plan source "
             "(MaintenanceEventTopicReader analogue: "
             "detector.maintenance_serde.TopicMaintenanceEventReader reads "
             "versioned plans from a Kafka topic; the file reader tails a "
             "JSON-lines file).")
    d.define("maintenance.event.topic", T.STRING,
             "__CruiseControlMaintenanceEvent", None, I.LOW,
             "Topic the maintenance-plan reader consumes.")
    d.define("maintenance.event.enable.idempotence", T.BOOLEAN, True, None,
             I.LOW, "Drop duplicate maintenance plans (IdempotenceCache).")
    d.define("maintenance.event.idempotence.retention.ms", T.LONG, 3_600_000,
             Range.at_least(1), I.LOW, "Idempotence-cache retention window.")
    d.define("maintenance.event.max.idempotence.cache.size", T.INT, 25,
             Range.at_least(1), I.LOW, "Idempotence-cache size bound.")
    d.define("maintenance.event.stop.ongoing.execution", T.BOOLEAN, False,
             None, I.LOW,
             "Maintenance plans may stop an in-flight execution.")
    d.define("broker.failure.detection.interval.ms", T.LONG, None, None,
             I.LOW, "Broker-failure detector interval "
             "(None = anomaly.detection.interval.ms).")
    d.define("disk.failure.detection.interval.ms", T.LONG, None, None, I.LOW,
             "Disk-failure detector interval (None = shared default).")
    d.define("topic.anomaly.detection.interval.ms", T.LONG, None, None, I.LOW,
             "Topic-anomaly detector interval (None = shared default).")
    d.define("kafka.broker.failure.detection.enable", T.BOOLEAN, True, None,
             I.LOW, "Metadata-polling broker failure detection (the ZK "
             "watcher variant is legacy and not implemented).")
    d.define("fixable.failed.broker.count.threshold", T.INT, 10,
             Range.at_least(0), I.LOW,
             "Self-healing declines when more brokers than this failed.")
    d.define("fixable.failed.broker.percentage.threshold", T.DOUBLE, 0.4,
             Range.between(0, 1), I.LOW,
             "Self-healing declines above this failed-broker fraction.")
    d.define("self.healing.goals", T.LIST, [], None, I.LOW,
             "Goal subset used when self-healing (empty = default goals).")
    d.define("self.healing.exclude.recently.demoted.brokers", T.BOOLEAN, True,
             None, I.LOW, "Self-healing skips recently demoted brokers for "
             "leadership.")
    d.define("self.healing.exclude.recently.removed.brokers", T.BOOLEAN, True,
             None, I.LOW, "Self-healing skips recently removed brokers for "
             "replica placement.")
    d.define("replication.factor.self.healing.skip.rack.awareness.check",
             T.BOOLEAN, False, None, I.LOW,
             "Allow self-healing RF changes to place multiple replicas of a "
             "partition in one rack when racks < RF "
             "(AnomalyDetectorConfig.java:309).")
    d.define("num.cached.recent.anomaly.states", T.INT, 10, Range.at_least(1),
             I.LOW, "Recent anomalies kept per type in the detector state.")
    d.define("anomaly.detection.allow.capacity.estimation", T.BOOLEAN, True,
             None, I.LOW, "Detectors may estimate missing broker capacity.")
    d.define("metric.anomaly.class", T.CLASS, None, None, I.LOW,
             "Metric-anomaly implementation override.")
    d.define("goal.violations.class", T.CLASS, None, None, I.LOW,
             "Goal-violation anomaly implementation override.")
    d.define("broker.failures.class", T.CLASS, None, None, I.LOW,
             "Broker-failure anomaly implementation override.")
    d.define("disk.failures.class", T.CLASS, None, None, I.LOW,
             "Disk-failure anomaly implementation override.")
    d.define("maintenance.event.class", T.CLASS, None, None, I.LOW,
             "Maintenance-event anomaly implementation override.")
    d.define("topic.anomaly.finder.class", T.LIST, None, None, I.LOW,
             "Topic-anomaly finder chain.")
    d.define("broker.failure.alert.threshold.ms", T.LONG, 900_000, Range.at_least(0), I.MEDIUM,
             "Age at which a broker failure alerts.")
    d.define("broker.failure.self.healing.threshold.ms", T.LONG, 1_800_000, Range.at_least(0),
             I.MEDIUM, "Age at which a broker failure auto-fixes.")
    d.define("failed.brokers.file.path", T.STRING, "fileStore/failed_brokers.json", None, I.LOW,
             "Persistence for failure times across restarts.")
    d.define("metric.anomaly.finder.class", T.CLASS,
             "cruise_control_tpu.detector.metric_anomaly.PercentileMetricAnomalyFinder",
             None, I.LOW, "MetricAnomalyFinder implementation.")
    d.define("metric.anomaly.percentile.upper.threshold", T.DOUBLE, 95.0,
             Range.between(0, 100), I.LOW, "")
    d.define("metric.anomaly.percentile.lower.threshold", T.DOUBLE, 2.0,
             Range.between(0, 100), I.LOW, "")
    d.define("slow.broker.bytes.in.rate.detection.threshold", T.DOUBLE, 1024.0,
             Range.at_least(0), I.LOW, "Min traffic for slow-broker relevance (KB/s).")
    d.define("slow.broker.demotion.score", T.INT, 5, Range.at_least(0), I.LOW,
             "Scoring threshold for demotion of slow brokers.")
    d.define("slow.broker.decommission.score", T.INT, 50, Range.at_least(0), I.LOW,
             "Scoring threshold for removal of slow brokers.")
    d.define("self.healing.target.topic.replication.factor", T.INT, None, None,
             I.LOW, "Desired RF enforced by the topic-anomaly detector; unset "
             "disables RF anomaly detection (TopicReplicationFactorAnomalyFinder).")
    d.define("topic.anomaly.topic.pattern", T.STRING, ".*", None, I.LOW,
             "Regex scoping which topics the RF anomaly finder enforces.")
    d.define("provisioner.class", T.CLASS,
             "cruise_control_tpu.detector.provisioner.BasicProvisioner",
             None, I.LOW, "Provisioner implementation.")

    # --- Web server / API (WebServerConfig.java) ---
    d.define("webserver.http.port", T.INT, 9090, Range.between(0, 65535), I.HIGH,
             "REST port.")
    d.define("webserver.http.address", T.STRING, "127.0.0.1", None, I.HIGH, "Bind address.")
    d.define("webserver.api.urlprefix", T.STRING, "/kafkacruisecontrol/*", None, I.LOW,
             "URL prefix of the REST API.")
    d.define("webserver.session.maxExpiryPeriodMs", T.LONG, 60_000, Range.at_least(1), I.LOW,
             "Async task session retention.")
    d.define("two.step.verification.enabled", T.BOOLEAN, False, None, I.MEDIUM,
             "Purgatory review flow on/off.")
    d.define("webserver.security.enable", T.BOOLEAN, False, None, I.MEDIUM, "")
    d.define("webserver.security.provider", T.CLASS,
             "cruise_control_tpu.api.security.BasicSecurityProvider",
             None, I.LOW, "SecurityProvider implementation.")
    d.define("webserver.auth.credentials.file", T.STRING, None, None, I.LOW,
             "htpasswd-style credentials for basic auth.")
    d.define("max.active.user.tasks", T.INT, 25, Range.at_least(1), I.LOW,
             "UserTaskManager active task cap.")
    d.define("completed.user.task.retention.time.ms", T.LONG, 86_400_000, Range.at_least(1),
             I.LOW, "Completed task retention.")
    d.define("max.cached.completed.user.tasks", T.INT, 100, Range.at_least(1),
             I.LOW, "Completed task cache size (default retention class).")
    d.define("max.cached.completed.kafka.monitor.user.tasks", T.INT, 20,
             Range.at_least(1), I.LOW,
             "Per-endpoint-class retention: monitor-type tasks "
             "(UserTaskManager.java:69-138).")
    d.define("max.cached.completed.kafka.admin.user.tasks", T.INT, 30,
             Range.at_least(1), I.LOW,
             "Per-endpoint-class retention: admin-type tasks.")
    d.define("max.cached.completed.cruise.control.monitor.user.tasks", T.INT,
             20, Range.at_least(1), I.LOW,
             "Per-endpoint-class retention: Cruise-Control-monitor tasks "
             "(STATE, USER_TASKS, REVIEW_BOARD, PERMISSIONS).")
    d.define("max.cached.completed.cruise.control.admin.user.tasks", T.INT,
             30, Range.at_least(1), I.LOW,
             "Per-endpoint-class retention: Cruise-Control-admin tasks "
             "(ADMIN, REVIEW, PAUSE/RESUME_SAMPLING, STOP, RIGHTSIZE).")
    d.define("completed.kafka.monitor.user.task.retention.time.ms", T.LONG,
             None, None, I.LOW,
             "Retention override for Kafka-monitor tasks (None = the "
             "completed.user.task.retention.time.ms default).")
    d.define("completed.kafka.admin.user.task.retention.time.ms", T.LONG,
             None, None, I.LOW,
             "Retention override for Kafka-admin tasks.")
    d.define("completed.cruise.control.monitor.user.task.retention.time.ms",
             T.LONG, None, None, I.LOW,
             "Retention override for Cruise-Control-monitor tasks.")
    d.define("completed.cruise.control.admin.user.task.retention.time.ms",
             T.LONG, None, None, I.LOW,
             "Retention override for Cruise-Control-admin tasks.")
    d.define("request.reason.required", T.BOOLEAN, False, None, I.LOW,
             "Require a non-empty reason parameter on proposal-executing "
             "POST endpoints (ExecutorConfig.REQUEST_REASON_REQUIRED).")
    d.define("webserver.http.header.size", T.INT, 65_536, Range.at_least(1),
             I.LOW, "Reject requests whose combined header bytes exceed "
             "this (431).")
    d.define("webserver.ssl.sts.enabled", T.BOOLEAN, False, None, I.LOW,
             "Send Strict-Transport-Security on HTTPS responses.")
    d.define("webserver.ssl.sts.include.subdomains", T.BOOLEAN, True, None,
             I.LOW, "includeSubDomains on the STS header.")
    d.define("webserver.ssl.sts.max.age", T.LONG, 31_536_000,
             Range.at_least(0), I.LOW, "STS max-age seconds.")
    d.define("provisioner.enable", T.BOOLEAN, True, None, I.LOW,
             "Right-sizing provisioner on/off: when disabled, RIGHTSIZE "
             "requests are refused and provision recommendations are not "
             "acted on (AnomalyDetectorConfig.PROVISIONER_ENABLE).")
    d.define("partition.metric.sample.aggregator.completeness.cache.size",
             T.INT, 5, Range.at_least(1), I.LOW,
             "Aggregation/completeness result cache entries kept on the "
             "partition aggregator (MonitorConfig).")
    d.define("broker.metric.sample.aggregator.completeness.cache.size",
             T.INT, 5, Range.at_least(1), I.LOW,
             "Aggregation/completeness result cache entries kept on the "
             "broker aggregator.")
    d.define("linear.regression.model.min.num.cpu.util.buckets", T.INT, 5,
             Range.at_least(1), I.LOW,
             "CPU-utilization buckets that must hold enough samples before "
             "the linear CPU model trains.")
    d.define("linear.regression.model.required.samples.per.bucket", T.INT,
             100, Range.at_least(1), I.LOW,
             "Samples a bucket needs before it counts toward training "
             "completeness (MonitorConfig default 100).")
    d.define("replica.to.broker.set.mapping.policy.class", T.CLASS, None,
             None, I.LOW,
             "Pluggable broker→broker-set mapping (default: the "
             "brokerSets.json file resolver; BrokerSetResolutionHelper).")
    d.define("inter.broker.replica.movement.rate.alerting.threshold",
             T.DOUBLE, 0.1, Range.at_least(0), I.LOW,
             "Alert when an execution's average inter-broker data movement "
             "rate (MB/s) falls below this.")
    d.define("intra.broker.replica.movement.rate.alerting.threshold",
             T.DOUBLE, 0.2, Range.at_least(0), I.LOW,
             "Alert when an execution's average intra-broker data movement "
             "rate (MB/s) falls below this.")
    d.define("webserver.request.maxBlockTimeMs", T.LONG, 10_000,
             Range.at_least(0), I.LOW,
             "How long a request blocks inline before returning 202 + "
             "User-Task-ID (the async wait).")
    d.define("webserver.session.maxExpiryTimeMs", T.LONG, 60_000,
             Range.at_least(1), I.LOW,
             "Session retention (accepted for config parity; the stdlib "
             "server is sessionless — tasks bind via User-Task-ID).")
    d.define("webserver.session.path", T.STRING, "/", None, I.LOW,
             "Session cookie path (accepted for config parity; sessionless "
             "server).")
    d.define("webserver.accesslog.enabled", T.BOOLEAN, True, None, I.LOW,
             "Log one line per handled request.")
    d.define("webserver.ui.diskpath", T.STRING, None, None, I.LOW,
             "Static Web-UI directory (accepted for config parity; no UI "
             "bundle ships with this framework).")
    d.define("webserver.ui.urlprefix", T.STRING, "/*", None, I.LOW,
             "UI URL prefix (accepted for config parity).")
    d.define("webserver.http.cors.enabled", T.BOOLEAN, False, None, I.LOW,
             "CORS headers on/off.")
    d.define("webserver.http.cors.origin", T.STRING, "*", None, I.LOW,
             "Access-Control-Allow-Origin value.")
    d.define("webserver.http.cors.allowmethods", T.STRING, "OPTIONS,GET,POST",
             None, I.LOW, "Access-Control-Allow-Methods value.")
    d.define("webserver.http.cors.exposeheaders", T.STRING, "User-Task-ID",
             None, I.LOW, "Access-Control-Expose-Headers value.")
    d.define("webserver.ssl.enable", T.BOOLEAN, False, None, I.MEDIUM,
             "Serve HTTPS (stdlib ssl; keystore location is a PEM "
             "cert+key file here, not a JKS).")
    d.define("webserver.ssl.keystore.location", T.STRING, None, None, I.MEDIUM,
             "PEM file with certificate + private key.")
    d.define("webserver.ssl.keystore.password", T.PASSWORD, None, None, I.LOW,
             "Private-key password.")
    d.define("webserver.ssl.keystore.type", T.STRING, "PEM", None, I.LOW,
             "Keystore format (PEM only in this implementation).")
    d.define("webserver.ssl.key.password", T.PASSWORD, None, None, I.LOW,
             "Key password (alias of keystore.password for PEM).")
    d.define("webserver.ssl.protocol", T.STRING, "TLS", None, I.LOW,
             "SSL protocol (accepted for parity; the stdlib server always "
             "negotiates via PROTOCOL_TLS_SERVER).")
    d.define("webserver.ssl.include.ciphers", T.LIST, None, None, I.LOW,
             "Cipher allowlist (None = library default).")
    d.define("webserver.ssl.exclude.ciphers", T.LIST, None, None, I.LOW,
             "Cipher denylist (accepted for parity; use include.ciphers — "
             "the stdlib ssl API takes an allowlist).")
    d.define("webserver.ssl.include.protocols", T.LIST, None, None, I.LOW,
             "Protocol allowlist (accepted for parity; PROTOCOL_TLS_SERVER "
             "negotiates the strongest shared version).")
    d.define("webserver.ssl.exclude.protocols", T.LIST, None, None, I.LOW,
             "Protocol denylist (accepted for parity; see include.protocols).")
    d.define("two.step.purgatory.retention.time.ms", T.LONG, 1_209_600_000,
             Range.at_least(1), I.LOW,
             "How long un-reviewed requests stay parked (Purgatory.java).")
    d.define("two.step.purgatory.max.requests", T.INT, 25, Range.at_least(1),
             I.LOW, "Max parked requests.")
    d.define("vertx.enabled", T.BOOLEAN, False, None, I.LOW,
             "Reference dual-stack flag; this implementation has one HTTP "
             "stack, so the flag is accepted and ignored.")
    d.define("jwt.authentication.provider.url", T.STRING, None, None, I.LOW,
             "Login redirect URL for JWT auth (token issuer).")
    d.define("jwt.cookie.name", T.STRING, None, None, I.LOW,
             "Cookie carrying the JWT (falls back to Bearer header).")
    d.define("jwt.auth.certificate.location", T.STRING, None, None, I.LOW,
             "Public key for token verification (RS256 requires the "
             "cryptography package; HS256 secret file otherwise).")
    d.define("jwt.expected.audiences", T.LIST, None, None, I.LOW,
             "Accepted aud claims (None = any).")
    d.define("spnego.principal", T.STRING, None, None, I.LOW,
             "Kerberos service principal for SPNEGO.")
    d.define("spnego.keytab.file", T.STRING, None, None, I.LOW,
             "Keytab backing the service principal.")
    d.define("trusted.proxy.services", T.LIST, None, None, I.LOW,
             "Service principals allowed to proxy (doAs) requests.")
    d.define("trusted.proxy.services.ip.regex", T.STRING, None, None, I.LOW,
             "Source-address pattern a trusted proxy must match.")
    d.define("trusted.proxy.spnego.fallback.enabled", T.BOOLEAN, False, None,
             I.LOW, "Fall back to SPNEGO auth when the caller is not a "
             "trusted proxy.")

    # --- Per-endpoint plugin bindings (CruiseControlParametersConfig /
    # CruiseControlRequestConfig: every endpoint's parameter parser and
    # request handler are config-swappable classes; None = built-in) ---
    for ep in ("bootstrap", "train", "load", "partition.load", "proposals",
               "state", "kafka.cluster.state", "user.tasks", "review.board",
               "permissions", "add.broker", "remove.broker",
               "fix.offline.replicas", "rebalance", "stop.proposal",
               "pause.sampling", "resume.sampling", "demote.broker", "admin",
               "review", "topic.configuration", "rightsize", "remove.disks",
               "fleet", "trace", "solver", "profile", "compare.futures",
               "heals", "forecast", "journeys", "slo", "redteam"):
        d.define(f"{ep}.parameters.class", T.CLASS, None, None, I.LOW,
                 f"Parameter-parsing plugin for the {ep} endpoint "
                 "(callable(query) -> params dict).")
        d.define(f"{ep}.request.class", T.CLASS, None, None, I.LOW,
                 f"Request-handling plugin for the {ep} endpoint "
                 "(instance.handle(facade, params, principal) -> body).")

    return d


_DEFINITION = _definition()


class CruiseControlConfig(AbstractConfig):
    """Merged, sanity-checked configuration (KafkaCruiseControlConfig.java)."""

    def __init__(self, props: Mapping[str, Any] | None = None):
        super().__init__(_DEFINITION, props or {})
        self._sanity_check()

    def _sanity_check(self) -> None:
        # KafkaCruiseControlConfig.sanityCheckGoalNames: hard.goals ⊆ goals,
        # anomaly.detection.goals ⊆ goals.
        goal_list = self.get_list("goals")
        if not goal_list:
            # KafkaCruiseControlConfig.java:161-166 — empty goals fail fast.
            raise ConfigException("goals must not be empty")
        goals = set(goal_list)
        for key in ("hard.goals", "anomaly.detection.goals"):
            subset = set(self.get_list(key))
            if not subset.issubset(goals):
                raise ConfigException(
                    f"{key} must be a subset of goals; extras: {sorted(subset - goals)}")
        if self.get_int("num.concurrent.partition.movements.per.broker") > \
                self.get_int("max.num.cluster.partition.movements"):
            raise ConfigException(
                "per-broker concurrent movements exceed the cluster-wide cap")
