"""Mean milliseconds a read waited in the task queue and for the model
build, from the program's journey segments."""


def read(ctx):
    done = [r for r in ctx.reads if r.status == 200]
    if not done:
        return None
    waited = sum(
        ctx.delta("journey_segment_seconds_sum", segment=segment,
                  endpoint=endpoint.upper())
        for segment in ctx.param["segments"]
        for endpoint in {r.endpoint for r in done})
    return 1000.0 * waited / len(done)
