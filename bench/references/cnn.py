"""The CNN client family: the plain reference of one HAPFL training round
for the CNN pool, and what the benchmark needs to know of the family.

A configuration names its family by the file in its `reference` key; the
harness loads that file (`bench/harness/spec.family`) and calls only the
functions in `spec.FAMILY_API`, so one family is one file.

The reference follows the paper (arXiv:2501.16966) and imports nothing of
src/repro:

  forward       3x3 SAME conv + ReLU + 2x2/2 max-pool per stage, flatten
                (NHWC row-major), fc1 + ReLU, fc2 (§V.A's CNN pool); each
                conv is one matmul over the shifted inputs, since the TPU
                compiler takes minutes and tens of GB for a float32
                convolution at "highest" precision
  mutual KD     L1 = l1 CE(local) + l2 KL(local || sg(lite)),
                L2 = l3 CE(lite) + l4 KL(lite || sg(local)),
                both minimised together (Eqs. 33-35)
  client step   SGD with momentum on {local, lite}: mu <- m mu + g,
                theta <- theta - lr mu
  entropy       H = -sum q log2 q over the client's label histogram
                (Eqs. 36-37)
  weights       W = (softmax(H) + softmax(acc)) / 2 (Eq. 38)
  fold          theta + sum_i W_i (theta_i - theta) (Eq. 39); LiteModels
                over all clients, local models per size group (Eq. 5)

`dtype` is the precision the whole computation runs in: float32 at
"highest" matmul precision is the reference; bfloat16 is the control.

The operation and byte counts of a client step (`step_flops`,
`step_bytes`) come from the config's widths, never from the program's.
Only the glue at the end (`check_config`, `check_data`, `sim_config`)
touches the program: it checks the program's pool and data against the
config and builds the program's simulation config.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

LAMBDAS = (0.4, 0.6, 0.5, 0.5)    # paper Table II


F32 = 4


def widths(config: Dict, size: str) -> Dict:
    """The stated widths of one model of the pool (`lite` or a size)."""
    w = config["pool"][size]
    return {"in_shape": config["in_shape"], "channels": w["channels"],
            "hidden": w["hidden"], "n_classes": config["n_classes"]}


def _precision(dtype):
    return "highest" if jnp.dtype(dtype) == jnp.float32 else None


def init_params(key, config: Dict, size: str):
    """He-normal weights, zero biases, of the config's model `size`, in the
    layout the server keeps: {conv: [...], conv_b: [...], fc1, fc1_b, fc2,
    fc2_b}."""
    w = widths(config, size)
    c_in = w["in_shape"][2]
    keys = jax.random.split(key, len(w["channels"]) + 2)
    p = {"conv": [], "conv_b": []}
    for i, c in enumerate(w["channels"]):
        p["conv"].append(jax.random.normal(keys[i], (3, 3, c_in, c))
                         * math.sqrt(2.0 / (9 * c_in)))
        p["conv_b"].append(jnp.zeros((c,), jnp.float32))
        c_in = c
    n = len(w["channels"])
    flat = (max(w["in_shape"][0] // 2 ** n, 1)
            * max(w["in_shape"][1] // 2 ** n, 1) * c_in)
    p["fc1"] = jax.random.normal(keys[-2], (flat, w["hidden"])) \
        * math.sqrt(2.0 / flat)
    p["fc1_b"] = jnp.zeros((w["hidden"],), jnp.float32)
    p["fc2"] = jax.random.normal(keys[-1], (w["hidden"], w["n_classes"])) \
        * math.sqrt(1.0 / w["hidden"])
    p["fc2_b"] = jnp.zeros((w["n_classes"],), jnp.float32)
    return p


def _conv3x3(x, k, prec):
    """SAME 3x3 convolution as one matmul over the 9 shifted copies of
    the input (tap-major, then input channel: HWIO order)."""
    b, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = [xp[:, i:i + h, j:j + w, :] for i in range(3) for j in range(3)]
    cols = jnp.stack(taps, axis=3).reshape(b, h, w, 9 * c)
    return jnp.dot(cols, k.reshape(9 * c, -1), precision=prec)


def _pool2x2(x):
    """2x2/2 max-pool; odd trailing rows and columns are dropped."""
    b, h, w, c = x.shape
    x = x[:, :h // 2 * 2, :w // 2 * 2]
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(params, x, dtype=jnp.float32):
    prec = _precision(dtype)
    x = x.astype(dtype)
    for k, b in zip(params["conv"], params["conv_b"]):
        x = _pool2x2(jax.nn.relu(_conv3x3(x, k.astype(dtype), prec)
                                 + b.astype(dtype)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["fc1"].astype(dtype), precision=prec)
                    + params["fc1_b"].astype(dtype))
    return jnp.dot(x, params["fc2"].astype(dtype), precision=prec) \
        + params["fc2_b"].astype(dtype)


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))


def _kl(p, q):
    lp = jax.nn.log_softmax(p.astype(jnp.float32), -1)
    lq = jax.nn.log_softmax(q.astype(jnp.float32), -1)
    return jnp.mean(jnp.sum(jnp.exp(lp) * (lp - lq), -1))


def mutual_loss(params, x, y, dtype=jnp.float32, lambdas=LAMBDAS):
    l1, l2, l3, l4 = lambdas
    a = forward(params["local"], x, dtype)
    b = forward(params["lite"], x, dtype)
    sg = jax.lax.stop_gradient
    return (l1 * _ce(a, y) + l2 * _kl(a, sg(b))
            + l3 * _ce(b, y) + l4 * _kl(b, sg(a)))


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "dtype"))
def client_step(params, mu, x, y, *, lr, momentum, dtype):
    """One SGD-momentum step; params and momentum are kept in `dtype`."""
    g = jax.grad(mutual_loss)(params, x, y, dtype)
    g = jax.tree_util.tree_map(lambda a: a.astype(dtype), g)
    mu = jax.tree_util.tree_map(lambda m, gg: (momentum * m + gg)
                                .astype(dtype), mu, g)
    params = jax.tree_util.tree_map(lambda p, m: (p - lr * m).astype(dtype),
                                    params, mu)
    return params, mu, g


def train_client(start, xs, ys, config: Dict, dtype=jnp.float32,
                 keep=None):
    """Train {local, lite} from `start` over the batches xs[i], ys[i], at
    the config's `lr` and `momentum`.

    `keep`, where given, is the number of rows of each batch to use (a
    control that leaves part of the batch out). Returns the trained params
    (float32 numpy) and the float64 norm of each leaf of the first step's
    gradient, in the order of the params' leaves."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), start)
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    g0 = None
    for x, y in zip(xs, ys):
        if keep is not None:
            x, y = x[:keep], y[:keep]
        p, mu, g = client_step(p, mu, jnp.asarray(x), jnp.asarray(y),
                               lr=config["lr"], momentum=config["momentum"],
                               dtype=dtype)
        if g0 is None:
            g0 = np.array([np.linalg.norm(np.asarray(a, np.float32)
                                          .astype(np.float64))
                           for a in jax.tree_util.tree_leaves(g)])
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  p), g0


@functools.partial(jax.jit, static_argnames=("dtype",))
def _argmax(params, x, dtype):
    return jnp.argmax(forward(params, x, dtype), -1)


def accuracy(params, x, y, dtype=jnp.float32, chunk: int = 256) -> float:
    """Share of rows whose largest logit is the label, in chunks of one
    shape (the last one zero-padded)."""
    hits = 0
    for i in range(0, len(x), chunk):
        xs = x[i:i + chunk]
        n = len(xs)
        xs = np.concatenate([xs, np.zeros((chunk - n,) + xs.shape[1:],
                                          xs.dtype)])
        pred = np.asarray(_argmax(params, jnp.asarray(xs), dtype))[:n]
        hits += int(np.sum(pred == y[i:i + n]))
    return hits / len(x)


def entropy(labels: np.ndarray, n_classes: int) -> float:
    counts = np.bincount(labels, minlength=n_classes).astype(np.float64)
    q = counts[counts > 0] / counts.sum()
    return float(-np.sum(q * np.log2(q)))


def _softmax(v):
    v = np.asarray(v, np.float64)
    e = np.exp(v - v.max())
    return e / e.sum()


def weights(entropies: Sequence[float], accs: Sequence[float]) -> np.ndarray:
    return 0.5 * (_softmax(entropies) + _softmax(accs))


def fold(theta, clients: Sequence, w: np.ndarray):
    """theta + sum_i w_i (theta_i - theta), in float64, one leaf at a time:
    a client's leaf is read (from its memory map) only while it is
    added."""
    w = np.asarray(w, np.float64) / np.sum(w)

    def leaf(t, *cs):
        t = np.asarray(t, np.float64)
        return (t + sum(wi * (np.asarray(c, np.float64) - t)
                        for wi, c in zip(w, cs))).astype(np.float32)
    return jax.tree_util.tree_map(leaf, theta, *clients)


def aggregate(lite, by_size: Dict, sizes: Sequence[str], trained: Sequence,
              entropies: Sequence[float], accs_local: Sequence[float],
              accs_lite: Sequence[float]):
    """Eq. 38-39 over LiteModels, Eq. 5 per size group over local models.
    `trained[i]` is client i's {local, lite}, as memory maps or arrays."""
    new_lite = fold(lite, [t["lite"] for t in trained],
                    weights(entropies, accs_lite))
    new = dict(by_size)
    for s in set(sizes):
        idx = [i for i, si in enumerate(sizes) if si == s]
        new[s] = fold(by_size[s], [trained[i]["local"] for i in idx],
                      weights([entropies[i] for i in idx],
                              [accs_local[i] for i in idx]))
    return new_lite, new


# ---------------------------------------------------------------------- #
# operations and bytes of one client step
# ---------------------------------------------------------------------- #
# A client step trains the local model and the LiteModel on one batch
# (Eqs. 33-35): a forward and a backward pass through each. A matmul-like
# layer costs 2*MACs forward and twice that backward (input and weight
# gradients), except the first layer, whose input needs no gradient.
# Elementwise work (ReLU, pooling, softmax, the optimizer) is not counted:
# it is a few percent and not what the chip's peak is quoted for.
def layer_flops(w: Dict) -> List[int]:
    """Forward operations per sample of each layer."""
    h, wd, c = w["in_shape"]
    out = []
    for co in w["channels"]:
        out.append(2 * h * wd * 9 * c * co)
        c, h, wd = co, h // 2, wd // 2
    flat = max(h, 1) * max(wd, 1) * c
    out += [2 * flat * w["hidden"], 2 * w["hidden"] * w["n_classes"]]
    return out


def param_count(w: Dict) -> int:
    h, wd, c = w["in_shape"]
    n = 0
    for co in w["channels"]:
        n += 9 * c * co + co
        c, h, wd = co, h // 2, wd // 2
    flat = max(h, 1) * max(wd, 1) * c
    return n + flat * w["hidden"] + w["hidden"] + \
        w["hidden"] * w["n_classes"] + w["n_classes"]


def train_flops_per_sample(w: Dict) -> int:
    f = layer_flops(w)
    return 3 * sum(f) - f[0]


def step_flops(config: Dict, size: str) -> int:
    """One client step of `size` + LiteModel at the config's batch."""
    return config["batch_size"] * (
        train_flops_per_sample(widths(config, size))
        + train_flops_per_sample(widths(config, "lite")))


def step_bytes(config: Dict, size: str) -> int:
    """Bytes a step has to move at the least: the batch in, and the
    parameters and momentum of both models read and written once."""
    h, wd, c = config["in_shape"]
    params = param_count(widths(config, size)) + \
        param_count(widths(config, "lite"))
    batch = config["batch_size"] * (h * wd * c * F32 + 4)
    return batch + 4 * params * F32


# ---------------------------------------------------------------------- #
# glue to the program under test
# ---------------------------------------------------------------------- #
def check_config(config: Dict) -> None:
    """Refuse to measure a model other than the one the config states: the
    program's pool must have the stated widths for every size."""
    from repro.models.cnn import cnn_pool
    pool = cnn_pool(config["dataset"])
    for size, want in config["pool"].items():
        if size not in pool:
            raise ValueError(f"cnn_pool({config['dataset']!r}) has no "
                             f"size {size!r}")
        got = pool[size]
        have = {"in_shape": list(got.in_shape), "channels": list(got.channels),
                "hidden": got.hidden, "n_classes": got.n_classes}
        stated = {"in_shape": config["in_shape"],
                  "channels": want["channels"], "hidden": want["hidden"],
                  "n_classes": config["n_classes"]}
        if have != stated:
            raise ValueError(f"cnn_pool({config['dataset']!r})[{size!r}]"
                             f" is {have}, the config states {stated}")


def check_data(config: Dict, data: Dict) -> None:
    """The environment's images must have the stated shape and dtype."""
    for split in ("x_train", "x_test"):
        x = data[split]
        if list(x.shape[1:]) != config["in_shape"] or \
                str(x.dtype) != config["input_dtype"]:
            raise ValueError(f"{split} is {x.dtype}{list(x.shape[1:])}, the "
                             f"config states {config['input_dtype']}"
                             f"{config['in_shape']}")


def sim_config(config: Dict, traffic: Dict, fleet_seed: int):
    """The program's simulation config for the config's pool and a traffic
    mix, its fleet drawn from `fleet_seed`."""
    from repro.fl import FLSimConfig
    return FLSimConfig(
        dataset=config["dataset"], n_clients=traffic["n_clients"],
        k_per_round=traffic["k_per_round"],
        max_speed_ratio=traffic["max_speed_ratio"],
        size_names=tuple(config["size_names"]),
        default_epochs=traffic["default_epochs"],
        batch_size=config["batch_size"],
        batches_per_epoch=config["batches_per_epoch"], lr=config["lr"],
        dirichlet_alpha=traffic["dirichlet_alpha"],
        n_train=traffic["n_train"], n_test=traffic["n_test"],
        seed=fleet_seed)
