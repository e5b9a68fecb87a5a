"""Replicas a proposal places on the new brokers, as a percentage of the
share of the cluster's replicas that falls to them when every broker holds
the same (``len(operation_brokers) * partitions * replication_factor /
brokers``, from the configuration's file), from the program's counter. An
empty plan reads 0, which ``onto_old_broker`` cannot tell from a scale-out
that was done; the lower edge of ReplicaDistributionGoal's band is
100 / 1.1 = 90.9 here, its upper edge 110.

A GUARD, not a mover: the valid range is 90.9-110 (the program's plan
reads 110.0 exactly, the band's upper edge, in every run so far), a
reading below it is a scale-out left undone, one above it a broken band.
It moves ``proposal_s`` in no direction; ``better: higher`` and ``moves``
are what the contract has to be given and what ISSUE 32 named. It stands
in for the count ``new_broker_underfilled`` that ``correct`` lacks
(PERF.md section 7), and is to be retired or declared anew with it.
A program without the counter (before PR 32) gives nothing to read."""


def read(ctx):
    name = "solver_scale_out_replicas_total"
    if not ctx.solves or not any(n == name for n, _labels in ctx.at_close):
        return None
    cfg = ctx.cfg
    share = len(cfg["operation_brokers"]) * cfg["partitions"] \
        * cfg["replication_factor"] / cfg["brokers"]
    return 100.0 * ctx.delta(name, onto="new") / len(ctx.solves) / share
