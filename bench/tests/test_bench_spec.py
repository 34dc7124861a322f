"""Cell lookup, the model family a configuration names, configuration
checks, the FLOP counter and the harness's refusal to run without a
TPU."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench.harness import device, spec
from bench.tests import checks

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_with_its_readers(name):
    cell = spec.load_cell(name)
    spec.family(cell.config).check_config(cell.config)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer and set(cell.readers) == {m["name"]
                                                    for m in cell.per_layer}
    assert cell.limits["limits"]


def test_a_width_that_differs_from_the_program_is_refused():
    cell = spec.load_cell(CELLS[0])
    cfg = json.loads(json.dumps(cell.config))
    cfg["pool"]["large"]["hidden"] += 1
    with pytest.raises(ValueError, match="hidden"):
        spec.family(cfg).check_config(cfg)


def test_data_of_another_shape_is_refused():
    import numpy as np
    cell = spec.load_cell(CELLS[0])
    shape = [2] + cell.config["in_shape"][:-1] + [7]
    bad = {"x_train": np.zeros(shape, np.float32),
           "x_test": np.zeros(shape, np.float32)}
    with pytest.raises(ValueError):
        spec.family(cell.config).check_data(cell.config, bad)


def test_a_cell_of_new_files_only_is_found_by_name(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "cnn-mnist-b", "source": "x",
                             "file": "bench/configs/cnn-mnist-b.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "mnist-b.new", "config": "cnn-mnist-b",
                               "traffic": "new_mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "new_metric", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "planning",
        "moves": bench["end_to_end"][0]["name"],
        "workloads": ["mnist-b.new"]})
    first = bench["end_to_end"][0]
    first["workloads"].append("mnist-b.new")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "cnn-mnist.json").read_text())
    (b / "configs" / "cnn-mnist-b.json").write_text(
        json.dumps(dict(cfg, name="cnn-mnist-b")))
    shutil.copy(b / "traffic" / "fleet_k64.json",
                b / "traffic" / "new_mix.json")
    shutil.copy(b / "limits" / "mnist.fleet_k64.json",
                b / "limits" / "mnist-b.new.json")
    (b / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    cell = spec.load_cell("mnist-b.new", root=tmp_path)
    assert cell.config["name"] == "cnn-mnist-b"
    assert [m["name"] for m in cell.end_to_end] == [first["name"], "setup_s"]
    assert cell.readers["new_metric"]({}) == 42.0
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no.such.cell", root=tmp_path)


def test_family_is_the_module_the_config_names():
    cell = spec.load_cell(CELLS[0])
    fam = spec.family(cell.config)
    assert fam.__file__ == str(spec.ROOT / cell.config["reference"])
    assert spec.family(dict(cell.config)) is fam
    assert all(callable(getattr(fam, f)) for f in spec.FAMILY_API)


def test_a_family_that_lacks_a_function_is_refused(tmp_path):
    src = (spec.BENCH / "references" / "cnn.py").read_text()
    for gone in ("step_bytes", "aggregate"):
        src = src.replace(f"def {gone}(", f"def _{gone}(")
    (tmp_path / "partial.py").write_text(src)
    cfg = dict(spec.load_cell(CELLS[0]).config, reference="partial.py")
    with pytest.raises(spec.SpecError, match="aggregate, step_bytes"):
        spec.family(cfg, root=tmp_path)
    with pytest.raises(spec.SpecError, match="no file"):
        spec.family(dict(cfg, reference="absent.py"), root=tmp_path)


def test_a_training_run_takes_the_model_from_the_family(monkeypatch):
    """The harness trains and seeds through the functions of the module the
    config names; wrapped copies there see every call."""
    from bench.harness import compare, train_cell
    cell = checks.file_cell("cnn-mnist", "fleet_k64", "mnist.fleet_k64",
                            checks.TRAIN)
    fam = spec.family(cell.config)
    calls = {"train_client": 0, "init_params": []}
    train, init = fam.train_client, fam.init_params

    def train_client(*args, **kw):
        calls["train_client"] += 1
        return train(*args, **kw)

    def init_params(key, config, size):
        calls["init_params"].append(size)
        return init(key, config, size)
    monkeypatch.setattr(fam, "train_client", train_client)
    monkeypatch.setattr(fam, "init_params", init_params)
    run = train_cell.TrainingRun(cell, 5)
    run.setup(warm=False)
    run.release()
    verdict = compare.verdict(run.check(), cell.limits["limits"])
    assert verdict["correct"]
    assert calls["train_client"] == cell.traffic["k_per_round"]
    assert sorted(calls["init_params"]) == sorted(
        ["lite"] + cell.config["size_names"])


def test_peak_table_refuses_an_unknown_kind():
    assert device.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v99")


@pytest.mark.parametrize("config,size", [("cnn-mnist", "small"),
                                         ("cnn-mnist", "large"),
                                         ("cnn-imagenet10", "small"),
                                         ("cnn-imagenet10", "large")])
def test_flop_counter_against_cost_analysis(config, size):
    """The counter takes matmul and convolution work only; XLA's count adds
    the elementwise work (ReLU, pooling, softmax, the optimizer), a few
    percent to a tenth more."""
    from repro.fl.batched import cohort_step_fns
    from repro.models.cnn import cnn_pool, init_cnn
    cfg = json.loads((spec.BENCH / "configs" / f"{config}.json").read_text())
    pool = cnn_pool(cfg["dataset"])
    step, init_opt = cohort_step_fns(pool[size], pool["lite"], cfg["lr"])
    p = {"local": init_cnn(jax.random.PRNGKey(0), pool[size]),
         "lite": init_cnn(jax.random.PRNGKey(1), pool["lite"])}
    x = jnp.zeros((cfg["batch_size"],) + tuple(cfg["in_shape"]))
    y = jnp.zeros((cfg["batch_size"],), jnp.int32)
    xla = jax.jit(step).lower(p, init_opt(p), x, y).compile() \
        .cost_analysis()["flops"]
    assert 0.85 <= spec.family(cfg).step_flops(cfg, size) / xla <= 1.0


def test_harness_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(spec.BENCH / "run.py"),
                        "--workload", CELLS[-1], "--seed", "3",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=spec.ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr
