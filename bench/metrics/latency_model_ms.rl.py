"""Planning: the latency.* spans (the numpy latency model: assessment
times, local training times, and the agents' rewards) per latency-only
round, in ms."""


def read(ctx):
    t = [v for n, v in ctx["spans"].items() if n.startswith("latency.")]
    return sum(t) / ctx["rounds"] * 1e3 if t and ctx["rounds"] else None
