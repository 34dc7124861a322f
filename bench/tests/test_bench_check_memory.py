"""The training check's host memory and the device of its reference, on a
fake client family: the per-client walk holds the globals and at most
three client models whatever the number of clients, where the whole round
held them all; a config's `reference_device` puts the float32 reference
on the host or on the chip at "highest" precision, and an unknown one is
refused."""
import json
import tracemalloc
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import device, spec, train_cell
from bench.harness.spill import ClientStore
from bench.references import cnn as ref_cnn
from bench.tests import checks

LEAVES, LEAF = 16, 2 ** 17       # a local model of 16 leaves of 512 KiB


def _model(rng, leaves, size):
    return {f"w{j}": rng.standard_normal(size, np.float32)
            for j in range(leaves)}


def _nbytes(tree):
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def _family(seen):
    """A client family of plain arrays: training scales every leaf."""
    def train_client(start, xs, ys, config, dtype, keep):
        seen.append((jnp.dtype(dtype).name,
                     jax.config.jax_default_matmul_precision,
                     jax.config.jax_default_device))
        trained = jax.tree_util.tree_map(
            lambda a: (a * np.float32(1.01)).astype(np.float32), start)
        return trained, np.ones(len(jax.tree_util.tree_leaves(start)))
    return SimpleNamespace(train_client=train_client,
                           entropy=ref_cnn.entropy,
                           aggregate=ref_cnn.aggregate)


def _first(k, where="host", seen=None):
    rng = np.random.default_rng(0)
    w0 = {"lite": _model(rng, 4, LEAF // 8),
          "large": _model(rng, LEAVES, LEAF)}
    clients = list(range(k))
    labels = rng.integers(0, 10, 10 * k)
    return train_cell.FirstRound(
        fam=_family([] if seen is None else seen),
        config={"n_classes": 10, "size_names": ["large"],
                "reference_device": where}, evals=False, plan=(clients, ["large"] * k, [1] * k), w0=w0,
        batches={c: (None, None) for c in clients},
        data={"x_train": None, "y_train": labels,
              "parts": {c: np.arange(10 * c, 10 * c + 10) for c in clients}})


def _program(first, directory):
    """The program's side, its clients spilled as set-up spills them."""
    store = ClientStore(directory)
    for i in range(len(first.plan[0])):
        store.put(i, jax.tree_util.tree_map(
            lambda a: (a * np.float32(1.02)).astype(np.float32),
            first.start(i)))
    return train_cell.Outputs("program", store.get, dict(first.w0), {})


def _peak(fn):
    tracemalloc.start()
    try:
        fn()
        return tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()


@pytest.mark.parametrize("k", [4, 16])
def test_walk_holds_the_globals_and_three_clients(tmp_path, k):
    first = _first(k)
    prog = _program(first, tmp_path / "program")
    client, globs = _nbytes(first.start(0)), _nbytes(first.w0)
    peak = _peak(lambda: train_cell.walk(first, [prog], tmp_path / "ref"))
    assert peak < globs + 3 * client


def test_whole_round_held_every_client(tmp_path):
    """The measure sees what the per-client walk saves: the whole round's
    reference alone holds all 16 clients."""
    first = _first(16)
    peak = _peak(lambda: checks.whole_round(first))
    assert peak > 16 * _nbytes(first.start(0))


def test_walk_numbers_of_the_fake_family(tmp_path):
    first = _first(4)
    res = train_cell.walk(first, [_program(first, tmp_path / "program")],
                          tmp_path / "ref")["program"]["numbers"]
    # every leaf moved by 2% where the reference moved it by 1% (in float32)
    assert res["client_change_gap"] == pytest.approx(1.0, rel=1e-4)
    assert res["client_median_gap"] == pytest.approx(1.0, rel=1e-4)


@pytest.mark.parametrize("where,precision", [("host", None),
                                             ("chip", "highest")])
def test_reference_device_places_the_float32_reference(tmp_path, where,
                                                       precision):
    seen = []
    first = _first(2, where, seen)
    prog = _program(first, tmp_path / "program")
    bf16 = train_cell.Reference(first, tmp_path / "bf16", "bf16",
                                jnp.bfloat16)
    train_cell.walk(first, [prog, bf16], tmp_path / "ref")
    place = (jax.devices("cpu") if where == "host" else jax.devices())[0]
    assert seen[0::2] == [("float32", precision, place)] * 2
    assert seen[1::2] == [("bfloat16", None, jax.devices()[0])] * 2


def test_reference_device_is_host_unless_the_config_says(tmp_path):
    assert spec.reference_device({}) == "host"
    assert spec.reference_device({"reference_device": "chip"}) == "chip"
    with pytest.raises(spec.SpecError, match="gpu"):
        spec.reference_device({"reference_device": "gpu"})
    with pytest.raises(ValueError, match="gpu"):
        with device.reference_device(jnp.float32, "gpu"):
            pass
    bench = {"workloads": [{"name": "x", "config": "c", "traffic": "t",
                            "chips": 1}],
             "configs": [{"name": "c", "file": "c.json"}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "c.json").write_text(json.dumps(
        {"name": "c", "reference_device": "gpu"}))
    with pytest.raises(spec.SpecError, match="reference_device"):
        spec.load_cell("x", root=tmp_path)
