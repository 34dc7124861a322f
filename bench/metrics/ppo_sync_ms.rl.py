"""Planning: the ppo.sync spans (the host waiting on the PPO agents'
device-to-host reads: each act's action and log-probability, each
update's metrics) per latency-only round, in ms."""


def read(ctx):
    t = ctx["spans"].get("ppo.sync")
    return t / ctx["rounds"] * 1e3 if t is not None and ctx["rounds"] \
        else None
