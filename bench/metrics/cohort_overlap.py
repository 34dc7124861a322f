"""Cohort engine: the share of size groups dispatched while the previous
group was still uncollected (the cohort.overlapped count over the
cohort.groups count), in %.

The counts are the program tracer's totals since the window began (the
cell clears the tracer after set-up); a context that carries `counts` is
read instead. A program whose tracer keeps no such counts gives None."""


def read(ctx):
    counts = ctx.get("counts")
    if counts is None:
        from repro.obs import trace
        counts = getattr(trace.current(), "counts", None) or {}
    groups = counts.get("cohort.groups")
    if not groups:
        return None
    return 100.0 * counts.get("cohort.overlapped", 0) / groups
