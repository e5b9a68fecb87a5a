"""Mean over the window's proposals of the score the served body
reports."""


def read(ctx):
    if not ctx.solves:
        return None
    return sum(float(s.body["summary"]["balancedness_after"])
               for s in ctx.solves) / len(ctx.solves)
