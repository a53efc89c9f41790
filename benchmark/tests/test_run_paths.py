"""Whole runs at the tiny preset (``--rehearse``: the harness's look for a
chip is skipped, everything else is driven), each in a process of its own.

* a throw-away configuration + traffic mix + per-layer metric, dropped in as
  new files and new manifest entries, is found and rehearsed with no edit to
  any file that was there;
* the lower-precision control comes out NOT correct;
* the timed path broken underneath (an optimizer step that returns its state
  unchanged; a token altered where it is produced) comes out NOT correct;
* without ``--rehearse`` and without a TPU the run exits non-zero and prints
  no result.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

BROKEN_TRAIN = """
from mxnet_tpu import fastpath
def tree_kernel(optimizer, mp_flags):
    return lambda ws, grads, states, ts, lrs, wds, extras: (ws, states)
fastpath.tree_kernel = tree_kernel
"""

BROKEN_DECODE = """
import jax.numpy as jnp
from mxnet_tpu import serving
_decode = serving.TinyDecoder.decode
def decode(self, *a, **k):
    logits, kp, vp = _decode(self, *a, **k)
    return jnp.roll(logits, 1, axis=-1), kp, vp
serving.TinyDecoder.decode = decode
"""


def _run(args, root=ROOT, patch="", env=None):
    code = ("import sys, runpy\nsys.path[:0] = [%r, %r]\n%s\n"
            "sys.argv = ['run.py'] + %r\n"
            "runpy.run_path(%r, run_name='__main__')\n"
            % (os.path.join(root, "benchmark"), ROOT, patch, args,
               os.path.join(root, "benchmark", "run.py")))
    full_env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    full_env.update(env or {})
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          env=full_env, capture_output=True, text=True,
                          timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, [json.loads(ln) for ln in lines]


def _compared(lines, what):
    return next(ln for ln in lines if ln.get("phase") == "compared"
                and ln["what"] == what)


def test_no_tpu_is_a_failure_not_a_cpu_fallback():
    proc, lines = _run(["--workload", "decoder_opt1p3b_chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not any("correct" in ln for ln in lines)
    assert "no TPU" in proc.stderr


def test_drop_in_config_traffic_and_layer_metric(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    before = {}
    for dirpath, _d, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(dirpath, name)
            before[path] = open(path, "rb").read()
    with open(os.path.join(BENCH, "configs",
                           "decoder_opt1p3b_widths.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    for key, val in tiny.items():
        cfg[key].update(val)
    cfg["name"] = "throwaway_decoder"
    with open(os.path.join(root, "benchmark", "configs",
                           "throwaway_decoder.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "throwaway_mix.json"), "w") as f:
        json.dump({"kind": "open_loop", "rate_per_s": 8,
                   "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
                   "output_len": {"dist": "fixed", "value": 6}}, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "throwaway_requests.py"), "w") as f:
        f.write("def read(run):\n    return run['counters']['requests']\n")
    manifest["configs"].append({
        "name": "throwaway_decoder", "source": "none", "reduced": [],
        "file": "benchmark/configs/throwaway_decoder.json", "why": "test"})
    manifest["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway_decoder",
        "traffic": "throwaway_mix", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if "workloads" in m and "decoder_opt1p3b_chat" in m["workloads"]:
            m["workloads"].append("throwaway_cell")
    manifest["per_layer"].append({
        "name": "throwaway_requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "decode_tok_per_s", "workloads": ["throwaway_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    proc, lines = _run(["--workload", "throwaway_cell", "--seed", "3",
                        "--rehearse"], root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True and lines[-1]["attempted"] == 16
    assert lines[-1]["device"]["platform"] == "cpu"
    assert lines[-1]["metrics"] == {}
    rehearsed = next(ln for ln in lines if ln.get("phase") == "rehearsed")
    assert ["throwaway_requests", True] in rehearsed["layer_metrics_readable"]
    assert all(open(p, "rb").read() == data for p, data in before.items())


@pytest.mark.parametrize("workload,patch,what", [
    ("decoder_opt1p3b_chat", BROKEN_DECODE,
     "served_token_widest_logit_gap_sd"),
    ("resnet50_b128_train_1chip", BROKEN_TRAIN,
     "param_change_norm_worst_leaf_rel_gap"),
])
def test_broken_timed_path_is_not_correct(workload, patch, what):
    proc, lines = _run(["--workload", workload, "--seed", "11",
                        "--rehearse"], patch=patch)
    assert lines and lines[-1]["correct"] is False, proc.stderr[-2000:]
    assert proc.returncode != 0
    assert _compared(lines, what)["ok"] is False


def test_sound_run_is_correct_and_prints_every_number_beside_its_limit():
    proc, lines = _run(["--workload", "decoder_opt1p3b_chat", "--seed", "12",
                        "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    rows = [ln for ln in lines if ln.get("phase") == "compared"]
    assert rows and all("value" in r and "limit" in r for r in rows)


@pytest.mark.parametrize("workload,seed,what", [
    # bf16 training fails whichever precision number it moves most at this
    # size (on the chip: the parameters' change, 8.3 against a limit of 0.9)
    ("resnet50_b128_train_1chip", "13", None),
    # a 24-layer toy rounds few tokens apart; seed 15 is one where bfloat16
    # puts first a token 0.043 sd below the reference's best (limit 0.02;
    # on the chip at full size every seed read 0.04 or more)
    ("decoder_opt1p3b_chat", "15", "CONTROL_served_token_widest_logit_gap_sd"),
])
def test_lower_precision_control_is_not_correct(workload, seed, what):
    proc, lines = _run(["--workload", workload, "--seed", seed, "--rehearse",
                        "--control"])
    assert lines and lines[-1]["correct"] is False, proc.stderr[-2000:]
    failed = [ln["what"] for ln in lines if ln.get("phase") == "compared"
              and not ln["ok"]]
    assert failed and (what is None or what in failed), failed
    assert all("gap" in w for w in failed), failed


def test_four_chip_cell_rehearses_on_four_virtual_devices():
    proc, lines = _run(["--workload", "resnet50_b512_train_4chip", "--seed",
                        "14", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is True
    assert lines[-1]["device"] == {"platform": "cpu", "kind": "cpu",
                                   "count": 4, "memory_peak_bytes": 0}
