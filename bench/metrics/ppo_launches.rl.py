"""Planning: the ppo.launches count (jitted programs called in the
planning layer: the key split and its unstacking, each act, each update)
per latency-only round.

The count is the program tracer's total since the window began (the cell
clears the tracer after set-up); a context that carries `counts` is read
instead. A program whose tracer keeps no counts gives None."""


def read(ctx):
    counts = ctx.get("counts")
    if counts is None:
        from repro.obs import trace
        counts = getattr(trace.current(), "counts", None) or {}
    n = counts.get("ppo.launches")
    return n / ctx["rounds"] if n is not None and ctx["rounds"] else None
