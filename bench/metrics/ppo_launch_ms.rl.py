"""Planning: the ppo.put and ppo.dispatch spans (moving the PPO agents'
inputs to the device and launching their programs) per latency-only
round, in ms."""

SPANS = ("ppo.put", "ppo.dispatch")


def read(ctx):
    s = ctx["spans"]
    if not any(n in s for n in SPANS) or not ctx["rounds"]:
        return None
    return sum(s.get(n, 0.0) for n in SPANS) / ctx["rounds"] * 1e3
