"""Valid candidates of the move rounds that an EARLIER goal's acceptance
vetoed, as a percentage of the valid candidates, from the program's
counters ``solver_round_candidates_total{stage="valid"|"accepted"}`` (the
flight recorder's ``valid`` and ``accepted`` sums, counted once a pass on
the fused route, where the recorder keeps no per-round ring). On three
racks at RF 3 the rack rule alone vetoes every destination outside the
replica's own zone: two columns of three where the columns know no racks.
A program without the counters (before PR 34) gives nothing to read."""


def read(ctx):
    name = "solver_round_candidates_total"
    if not ctx.solves or not any(n == name for n, _labels in ctx.at_close):
        return None
    valid = ctx.delta(name, stage="valid")
    if not valid:
        return None
    return 100.0 * (valid - ctx.delta(name, stage="accepted")) / valid
