"""Shared by the test_bench_check_* files: `correct` at a size a test run
can hold, on the CPU. A sound run passes; the control (the reference in
bfloat16) and each fault the cells can have, planted beneath the harness's
wrappers, fail under the cells' own limits.

Faults: a step that returns its state unchanged, half of every batch left
out, and an answer altered where it is produced. The cells run on one chip,
so there is no exchange between chips to leave out.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare, device, fleet, rl_cell, spec, train_cell

TRAIN = {"n_clients": 6, "k_per_round": 3, "default_epochs": 2,
         "n_train": 360, "n_test": 60, "rl_warmup_rounds": 5,
         "plan_horizon": 1, "trace_rounds": 1}
RL = {"n_clients": 8, "k_per_round": 4, "n_train": 400, "n_test": 60,
      "check_rounds": 10, "trace_rounds": 5}


def shrunk_cell(name, shrink):
    """A cell of BENCHMARK.json at a test size."""
    return _shrunk(spec.load_cell(name), shrink)


def file_cell(config, traffic, name, shrink):
    """A cell from its files alone (kept in bench/ while the cell is out of
    BENCHMARK.json), at a test size."""
    read = lambda *parts: json.loads(spec.BENCH.joinpath(*parts).read_text())
    cell = spec.Cell(name=name, chips=1,
                     config=read("configs", f"{config}.json"),
                     traffic=read("traffic", f"{traffic}.json"),
                     limits=read("limits", f"{name}.json"),
                     end_to_end=[], per_layer=[])
    return _shrunk(cell, shrink)


def _shrunk(cell, shrink):
    # batch 8: the server's engine="auto" picks the batched engine on a CPU
    cell.config = dict(cell.config, batch_size=8)
    cell.traffic = dict(cell.traffic, **shrink)
    return cell


def with_fault(monkeypatch, plant):
    build = fleet.build

    def planted(*args, **kw):
        env, srv, shadow, seed = build(*args, **kw)
        plant(srv)
        return env, srv, shadow, seed
    monkeypatch.setattr(fleet, "build", planted)


def training_numbers(cell, seed=5):
    """The numbers of a run's check, as `bench/run.py` takes them."""
    run = train_cell.TrainingRun(cell, seed)
    run.setup(warm=False)
    run.release()
    return run.check()


def training_verdict(cell, seed=5):
    return compare.verdict(training_numbers(cell, seed),
                           cell.limits["limits"])


def whole_round(first, dtype=jnp.float32, keep=None):
    """The reference's first round with every client held at once: the
    training check as it was before it went one client at a time, kept as
    the reference of the per-client walk."""
    fam, where = first.fam, spec.reference_device(first.config)
    clients, sizes, _ = first.plan
    trained, grads = [], []
    for i, c in enumerate(clients):
        xs, ys = first.batches[int(c)]
        with device.reference_device(dtype, where):
            p, g = fam.train_client(first.start(i), xs, ys, first.config,
                                    dtype, keep)
        trained.append(p)
        grads.append(g)
    data = first.data
    x_tr, y_tr, parts = data["x_train"], data["y_train"], data["parts"]
    ents = [fam.entropy(y_tr[parts[c]], first.config["n_classes"])
            for c in clients]
    accs = {}
    if first.evals:
        for c, p in zip(clients, trained):
            for which in ("local", "lite"):
                accs[("client", which, int(c))] = fam.accuracy(
                    p[which], x_tr[parts[c]], y_tr[parts[c]], dtype)
    acc_loc = [accs.get(("client", "local", int(c)), 0.0) for c in clients]
    acc_lite = [accs.get(("client", "lite", int(c)), 0.0) for c in clients]
    with device.reference_device(dtype, where):
        lite, by_size = fam.aggregate(
            first.w0["lite"],
            {s: first.w0[s] for s in first.config["size_names"]}, sizes,
            trained, ents, acc_loc, acc_lite)
    after = {"lite": lite, **by_size}
    if first.evals:
        for name, p in after.items():
            accs[("global", name)] = fam.accuracy(
                p, data["x_test"], data["y_test"], dtype)
    return {"trained": trained, "grads": grads, "after": after, "accs": accs}


def whole_outputs(side, n_clients):
    """A side of the walk as a whole round in memory."""
    return {"trained": [side.client(i) for i in range(n_clients)],
            "after": side.after(), "accs": side.accs}


def whole_numbers(first, outputs, ref):
    """One whole round's numbers against the reference's, as the check
    took them before it went one client at a time."""
    _, sizes, _ = first.plan
    client = [compare.change_gaps(outputs["trained"][i], ref["trained"][i],
                                  first.start(i), ref["grads"][i])
              for i in range(len(sizes))]
    glob = [compare.change_gaps(outputs["after"][n], ref["after"][n],
                                first.w0[n])
            for n in ["lite"] + sorted(set(sizes))]
    out = {"client_change_gap": max(float(np.nanmax(g)) for g in client),
           "client_median_gap": max(float(np.nanmedian(g)) for g in client),
           "global_change_gap": max(float(np.nanmax(g)) for g in glob),
           "global_median_gap": max(float(np.nanmedian(g)) for g in glob)}
    if ref["accs"]:
        out["accuracy_gap"] = max(abs(outputs["accs"][key] - v)
                                  for key, v in ref["accs"].items())
    return out


def rl_verdict(cell, seed=5):
    run = rl_cell.RLRun(cell, seed)
    run.setup()
    prog = run.program_outputs()
    run.release()
    return compare.verdict(run.numbers(prog, run.reference()),
                           cell.limits["limits"])


def unchanged(srv):
    def dispatch(size, start, xs, ys, mask):
        return jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p, (xs.shape[0],) + p.shape), start)
    srv.batched_engine._dispatch = dispatch


def half_batch(srv):
    orig = srv.batched_engine._dispatch

    def dispatch(size, start, xs, ys, mask):
        b = xs.shape[2] // 2
        return orig(size, start, xs[:, :, :b], ys[:, :, :b], mask)
    srv.batched_engine._dispatch = dispatch


def altered(srv):
    orig = srv.batched_engine._dispatch

    def dispatch(size, start, xs, ys, mask):
        out = orig(size, start, xs, ys, mask)
        out["local"]["fc2"] = out["local"]["fc2"].at[0].add(0.05)
        return out
    srv.batched_engine._dispatch = dispatch


def agents(srv):
    return srv.allocator.agent, srv.intensity.agent


def rl_unchanged(srv):
    for agent in agents(srv):
        orig = agent._update
        agent._update = (lambda o: lambda p, s, b: (p, s, o(p, s, b)[2]))(orig)


def rl_half_batch(srv):
    for agent in agents(srv):
        orig = agent._update
        agent._update = (lambda o: lambda p, s, b: o(
            p, s, {k: v[:v.shape[0] // 2] for k, v in b.items()}))(orig)


def rl_altered(srv):
    agent = srv.allocator.agent
    orig = agent._act

    def act(params, key, state, deterministic):
        action, lp = orig(params, key, state, deterministic)
        return action.at[0].set(1 - action[0]), lp
    agent._act = act


def sound_and_control(cell, seed=5):
    """The verdicts of a sound run and of the control on the same first
    steps."""
    lim = cell.limits["limits"]
    if not cell.traffic["latency_only"]:
        run = train_cell.TrainingRun(cell, seed)
        run.setup(warm=False)
        run.release()
        try:
            res = run.readings([run.program(),
                                run.reference("control", jnp.bfloat16)])
        finally:
            run.close()
        return (compare.verdict(res["program"]["numbers"], lim),
                compare.verdict(res["control"]["numbers"], lim))
    run = rl_cell.RLRun(cell, seed)
    run.setup()
    prog = run.program_outputs()
    run.release()
    ref = run.reference()
    return (compare.verdict(run.numbers(prog, ref), lim),
            compare.verdict(run.numbers(run.reference(jnp.bfloat16), ref),
                            lim))
