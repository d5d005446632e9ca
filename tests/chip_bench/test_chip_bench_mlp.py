"""A configuration of another model kind comes into the benchmark through
files alone: the program's tanh MLP chain, with its configuration, its
plain reference and its cell written to a directory of their own, runs
through the unchanged harness on the CPU and comes out correct."""
import json
import textwrap

import pytest

from benchmarks.chip import peaks, registry, run

REFERENCE = '''
"""Plain reference of a dense tanh chain ending in a linear classifier,
and of its first pipelined SGD steps, in float32 at HIGHEST."""
import jax
import jax.numpy as jnp
import numpy as np

SPEC_KEYS = ("num_layers", "width", "in_dim", "num_classes", "noise")
HIGHEST = jax.lax.Precision.HIGHEST


def _dims(num_layers, width, in_dim, num_classes):
    return [(in_dim if j == 0 else width,
             num_classes if j == num_layers - 1 else width)
            for j in range(num_layers)]


def init_params(seed, *, num_layers, width, in_dim, num_classes, **_):
    ks = jax.random.split(jax.random.PRNGKey(seed), num_layers)
    return [{"w": jax.random.normal(ks[j], (a, b)) / np.sqrt(a),
             "b": jnp.zeros((b,))}
            for j, (a, b) in enumerate(_dims(num_layers, width, in_dim,
                                             num_classes))]


def make_batches(seed, count, batch, *, in_dim, num_classes, noise, **_):
    rng = np.random.default_rng(seed)
    templates = rng.normal(0, 1, (num_classes, in_dim)).astype(np.float32)
    out = []
    for _ in range(count):
        labels = rng.integers(0, num_classes, batch)
        x = templates[labels] + noise * rng.normal(
            0, 1, (batch, in_dim)).astype(np.float32)
        out.append({"x": x.astype(np.float32),
                    "labels": labels.astype(np.int32)})
    return out


def loss(params, x, labels):
    h = x
    for j, p in enumerate(params):
        h = jnp.dot(h, p["w"], precision=HIGHEST) + p["b"]
        if j < len(params) - 1:
            h = jnp.tanh(h)
    logp = jax.nn.log_softmax(h)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


_loss_and_grad = jax.jit(jax.value_and_grad(loss))


def first_steps(params, batches, *, lr, n_stages, steps):
    versions, losses, grads = [params], [], []
    for k in range(steps):
        b = batches[k]
        val, g = _loss_and_grad(versions[max(0, k - n_stages + 1)],
                                jnp.asarray(b["x"]), jnp.asarray(b["labels"]))
        losses.append(float(val))
        grads.append(g)
        versions.append(jax.tree.map(lambda p, d: p - lr * d,
                                     versions[-1], g))
    return {"losses": losses, "grad0": grads[0], "versions": versions}


def leaf_sizes(params):
    return [[int(np.prod(a.shape)) for a in jax.tree.leaves(p)]
            for p in params]


def flat_layers(params):
    return [np.concatenate([np.ravel(np.asarray(a, np.float32))
                            for a in jax.tree.leaves(p)]) for p in params]


def forward_flops_per_sample(*, num_layers, width, in_dim, num_classes, **_):
    return float(sum(2 * a * b for a, b in _dims(num_layers, width, in_dim,
                                                 num_classes)))


def param_count(*, num_layers, width, in_dim, num_classes, **_):
    return sum(a * b + b for a, b in _dims(num_layers, width, in_dim,
                                           num_classes))
'''

CONFIG = {
    "name": "mlp-tanh", "reference": "mlp_tanh",
    "model": {"kind": "mlp", "num_layers": 6, "width": 64, "in_dim": 8,
              "num_classes": 4, "noise": 0.3},
    "control_precision": "bf16", "reduced": [],
}

CELL = {
    "config": "mlp-tanh", "chips": 1, "workers": 3,
    "capacities": [1.0, 1.0, 1.0], "batch": 16, "data_batches": 8,
    "lr": 0.05, "chain_every": 5, "global_every": 10,
    "detect_timeout": 2.0, "segment_timeout": 600.0, "warmup_batches": 25,
    "horizon_batches": 20000, "check_steps": 6,
    # the cell cuts the configuration's width
    "width": 16,
    "limits": {"loss_gap": 0.007, "grad_gap_median": 0.05,
               "change_gap_median": 0.05},
}

METRICS = {
    "end_to_end": [
        {"name": "samples_per_s", "unit": "samples/s",
         "workloads": ["mlp.steady"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "mfu", "unit": "%", "moves": "samples_per_s",
         "workloads": ["mlp.steady"]}],
}


def _harness_files():
    return sorted((p.relative_to(registry.HERE), p.stat().st_mtime_ns)
                  for p in registry.HERE.rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts)


@pytest.fixture
def mlp_dir(tmp_path, monkeypatch):
    for sub, name, text in (
            ("configs", "mlp-tanh.json", json.dumps(CONFIG)),
            ("references", "mlp_tanh.py", textwrap.dedent(REFERENCE)),
            ("workloads", "mlp.steady.json", json.dumps(CELL))):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / name).write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(METRICS))
    monkeypatch.setattr(registry, "DIRS", [tmp_path, registry.HERE])
    monkeypatch.setattr(registry, "BENCHMARK", tmp_path / "BENCHMARK.json")
    # a peak for the CPU, so that mfu has something to divide by
    monkeypatch.setattr(peaks, "peaks_for", lambda platform, kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    return tmp_path


def test_mlp_configuration_runs_through_files_alone(mlp_dir, compile_cache):
    before = _harness_files()
    cell, ref = run.prepare(registry.load_cell("mlp.steady"))
    assert cell["model_kind"] == "mlp"
    assert cell["spec"] == {"num_layers": 6, "width": 16, "in_dim": 8,
                            "num_classes": 4, "noise": 0.3}
    assert run.run_config(cell, 3).workload.width == 16
    assert ref.param_count(**cell["spec"]) == sum(
        p.size for layer in ref.init_params(3, **cell["spec"])
        for p in layer.values())
    outs = [run.run_cell(registry.load_cell("mlp.steady"), 3, 1.0, trace,
                         require_chip=False, cache_dir=compile_cache)
            for trace in (False, True)]
    for out in outs:
        assert out["correct"] is True, out["checks"]
    assert set(outs[0]["metrics"]) == {"samples_per_s", "setup_s"}
    assert set(outs[1]["metrics"]) == {"mfu"}
    assert outs[0]["metrics"]["samples_per_s"]["value"] > 0
    assert outs[1]["metrics"]["mfu"]["value"] > 0
    assert _harness_files() == before
