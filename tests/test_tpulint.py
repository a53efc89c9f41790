"""Tests for tools/tpulint — the AST-based TPU-correctness linter.

Pure AST analysis: no JAX import, no device work — tier-1 fast by
construction. Each pass gets positive + negative fixtures; suppression,
baseline, the repo-wide gate, and the CLI exit-code contract are covered.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.tpulint import core  # noqa: E402
from tools.tpulint.cli import filter_to_scope, lint_paths, main  # noqa: E402
from tools.tpulint.core import (DEFAULT_BASELINE, apply_baseline,  # noqa: E402
                                baseline_counts, collect_files, lint_files,
                                lint_source, load_baseline, write_baseline)


def lint(src, rule=None, relpath="mxnet_tpu/fake.py"):
    """Lint a snippet; returns findings (optionally for one rule)."""
    findings = lint_source(relpath, textwrap.dedent(src),
                           passes=[rule] if rule else None)
    return findings


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

def test_host_sync_asnumpy_in_loop():
    found = lint("""
        def f(batches):
            out = []
            for b in batches:
                out.append(b.asnumpy())
            return out
    """, "host-sync")
    assert len(found) == 1 and found[0].line == 5


def test_host_sync_float_of_call_in_loop():
    found = lint("""
        def f(xs):
            total = 0.0
            while xs:
                total += float(xs.pop().sum())
            return total
    """, "host-sync")
    assert len(found) == 1


def test_host_sync_in_jit_even_outside_loop():
    found = lint("""
        import jax

        @jax.jit
        def step(x):
            return x * x.item()
    """, "host-sync")
    assert len(found) == 1 and "trace time" in found[0].message


def test_host_sync_jit_reaches_helpers_transitively():
    found = lint("""
        import jax, numpy as np

        def helper(x):
            return np.asarray(x)

        @jax.jit
        def step(x):
            return helper(x) + 1
    """, "host-sync")
    assert len(found) == 1 and found[0].line == 5


def test_host_sync_negative():
    assert not lint("""
        def f(batches):
            x = batches[0].asnumpy()      # outside any loop: one sync, fine
            n = float(len(batches))       # len() never touches the device
            for b in batches:
                n += 1.0
            return x, n
    """, "host-sync")


def test_host_sync_comprehension_counts_as_loop():
    found = lint("""
        def f(batches):
            return [b.asnumpy() for b in batches]
    """, "host-sync")
    assert len(found) == 1


# ---------------------------------------------------------------------------
# tracer-leak
# ---------------------------------------------------------------------------

def test_tracer_leak_positive():
    found = lint("""
        import jax, os, time

        @jax.jit
        def step(x):
            print("step!")
            t = time.time()
            flag = os.environ.get("MXNET_FLAG")
            return x + t
    """, "tracer-leak")
    msgs = " ".join(f.message for f in found)
    assert len(found) == 3
    assert "print" in msgs and "time.time" in msgs and "os.environ" in msgs


def test_tracer_leak_global_and_wrapped_lambda():
    found = lint("""
        import jax

        _calls = 0

        def bump(x):
            global _calls
            _calls += 1
            return x

        f = jax.jit(lambda x: bump(x) + 1)
    """, "tracer-leak")
    assert len(found) == 1 and "global _calls" in found[0].message


def test_tracer_leak_curried_partial_wrap():
    found = lint("""
        import jax
        from functools import partial

        def step(x):
            print("traced")
            return x

        fast_step = partial(jax.jit, donate_argnums=0)(step)
    """, "tracer-leak")
    assert len(found) == 1 and "print" in found[0].message


def test_tracer_leak_partial_decorator_and_np_random():
    found = lint("""
        import jax, numpy as np
        from functools import partial

        @partial(jax.jit, static_argnums=0)
        def step(n, x):
            return x + np.random.rand(n)
    """, "tracer-leak")
    assert len(found) == 1 and "np.random.rand" in found[0].message


def test_tracer_leak_negative_outside_jit():
    assert not lint("""
        import os, time

        def host_loop(x):
            print("fine here")
            return x, time.time(), os.getenv("HOME")
    """, "tracer-leak")


# ---------------------------------------------------------------------------
# dtype-drift
# ---------------------------------------------------------------------------

def test_dtype_drift_positive():
    found = lint("""
        import numpy as np
        import jax.numpy as jnp

        def f(x):
            return np.zeros(3, dtype=np.float64) + x.astype(jnp.float64)
    """, "dtype-drift")
    assert len(found) == 2


def test_dtype_drift_registry_exempt():
    assert not lint("""
        import jax.numpy as jnp

        DTYPE_NP = {
            "float64": jnp.float64,
            "float32": jnp.float32,
        }
    """, "dtype-drift")


def test_dtype_drift_negative():
    assert not lint("""
        import numpy as np

        def f(x):
            return x.astype(np.float32)
    """, "dtype-drift")


# ---------------------------------------------------------------------------
# native-guard
# ---------------------------------------------------------------------------

def test_native_guard_unguarded_assign():
    found = lint("""
        from mxnet_tpu import _native

        def stats():
            lib = _native.get_lib()
            return lib.MXTPUStorageStats()
    """, "native-guard")
    assert len(found) == 1 and "never checked" in found[0].message


def test_native_guard_guarded_variants():
    assert not lint("""
        from mxnet_tpu import _native

        def a():
            lib = _native.get_lib()
            if lib is None:
                return 0
            return lib.f()

        def b():
            lib = _native.get_lib()
            return lib.f() if lib is not None else 0

        def c():
            lib = _native.get_lib()
            if not lib:
                return 0
            return lib.f()

        def d():
            lib = _native.get_lib()
            return getattr(lib, "_name", None) or "unavailable"

        def e():
            return _native.get_lib() is not None
    """, "native-guard")


def test_native_guard_return_forward_and_direct_use():
    found = lint("""
        from mxnet_tpu import _native

        def forward():
            return _native.get_lib()

        def direct():
            return _native.get_lib().f()
    """, "native-guard")
    assert len(found) == 2
    assert any("forwards an unguarded Optional" in f.message for f in found)
    assert any("used directly" in f.message for f in found)


# ---------------------------------------------------------------------------
# env-knob
# ---------------------------------------------------------------------------

def test_env_knob_positive_reads():
    found = lint("""
        import os

        A = os.environ.get("MXNET_A", "1")
        B = os.getenv("MXNET_B")
        C = os.environ["MXNET_C"]
        D = os.environ.setdefault("MXNET_D", "x")
    """, "env-knob")
    assert len(found) == 4


def test_env_knob_mutations_not_flagged():
    assert not lint("""
        import os

        os.environ["MXNET_A"] = "1"
        os.environ.pop("MXNET_B", None)
        del os.environ["MXNET_C"]
    """, "env-knob")


def test_env_knob_scoped_to_mxnet_tpu():
    src = """
        import os
        A = os.environ.get("MXNET_A")
    """
    assert lint(src, "env-knob", relpath="mxnet_tpu/x.py")
    assert not lint(src, "env-knob", relpath="tools/x.py")
    assert not lint(src, "env-knob", relpath="mxnet_tpu/base.py")


# ---------------------------------------------------------------------------
# swallowed-error
# ---------------------------------------------------------------------------

def test_swallowed_error_positive_variants():
    found = lint("""
        def f(q):
            try:
                q.get()
            except Exception:
                pass
            while True:
                try:
                    q.get()
                except:
                    continue
            try:
                q.get()
            except (ValueError, BaseException):
                ...
    """, "swallowed-error")
    assert len(found) == 3


def test_swallowed_error_negative_handled_or_narrow():
    assert not lint("""
        import queue

        def f(q, log):
            try:
                q.get()
            except queue.Empty:
                pass
            try:
                q.get()
            except Exception as exc:
                log.warning("boom: %s", exc)
            try:
                q.get()
            except Exception:
                return None
            try:
                q.get()
            except Exception:
                raise
    """, "swallowed-error")


def test_swallowed_error_scoped_to_runtime_package():
    src = """
        def f(q):
            try:
                q.get()
            except Exception:
                pass
    """
    assert lint(src, "swallowed-error", relpath="mxnet_tpu/x.py")
    assert not lint(src, "swallowed-error", relpath="tools/x.py")


def test_swallowed_error_suppressible():
    found = lint("""
        def __del__(self):
            try:
                self.close()
            except Exception:  # tpulint: disable=swallowed-error
                pass
    """, "swallowed-error")
    assert not found


# ---------------------------------------------------------------------------
# oom-masking
# ---------------------------------------------------------------------------

def test_oom_masking_positive_broad_and_xla():
    found = lint("""
        import telemetry

        def step(fn, x, log):
            try:
                return telemetry.jit_call("s", fn, x)
            except Exception as exc:
                log.warning("boom: %r", exc)
                return None

        def fetch(arrays, XlaRuntimeError):
            try:
                return fetch_host(arrays)
            except XlaRuntimeError:
                return None
    """, "oom-masking")
    assert len(found) == 2
    assert all("hbm.classify" in f.message for f in found)


def test_oom_masking_negative_routed_or_reraised():
    assert not lint("""
        import telemetry
        from mxnet_tpu.resilience import hbm

        def survives(fn, x):
            try:
                return telemetry.jit_call("s", fn, x)
            except Exception as exc:
                if not hbm.oom_survival("s", exc):
                    raise
                return None

        def reraises(fn, x, log):
            try:
                return telemetry.jit_call("s", fn, x)
            except Exception as exc:
                log.warning("boom: %r", exc)
                raise

        def classifies(fn, x, log):
            try:
                return telemetry.jit_call("s", fn, x)
            except Exception as exc:
                kind = hbm.classify(exc)
                log.warning("kind=%s", kind)
                return None
    """, "oom-masking")


def test_oom_masking_needs_dispatch_in_try():
    # a broad catch around host-only work is swallowed-error's beat, not
    # an OOM mask — no dispatch/transfer call, no finding
    assert not lint("""
        def f(q, log):
            try:
                q.get()
            except Exception as exc:
                log.warning("boom: %r", exc)
                return None
    """, "oom-masking")


def test_oom_masking_narrow_catch_and_scope():
    src = """
        import telemetry

        def step(fn, x):
            try:
                return telemetry.jit_call("s", fn, x)
            except KeyError:
                return None
    """
    assert not lint(src, "oom-masking")
    broad = src.replace("KeyError", "Exception")
    assert lint(broad, "oom-masking", relpath="mxnet_tpu/x.py")
    assert not lint(broad, "oom-masking", relpath="tools/x.py")


OOM_BUGS = (REPO / "tests" / "fixtures" / "tpulint_oom_bugs.py").read_text()


def test_oom_masking_seeded_fixture():
    found = lint_source("mxnet_tpu/_oom_bugs.py", OOM_BUGS,
                        passes=["oom-masking"])
    lines = sorted(f.line for f in found)
    assert len(found) == 2
    # the two seeded masks fire; the routed/re-raising/narrow handlers
    # below them stay clean
    texts = [OOM_BUGS.splitlines()[ln - 1] for ln in lines]
    assert all("BUG" in t for t in texts)


# ---------------------------------------------------------------------------
# suppression + baseline
# ---------------------------------------------------------------------------

def test_inline_suppression():
    src = """
        import os
        A = os.environ.get("MXNET_A")  # tpulint: disable=env-knob -- justified
        B = os.environ.get("MXNET_B")  # tpulint: disable=all
        C = os.environ.get("MXNET_C")  # tpulint: disable=host-sync (wrong rule)
    """
    found = lint(src, "env-knob")
    assert len(found) == 1 and found[0].line == 5


def test_baseline_roundtrip(tmp_path):
    src_v1 = "import os\nA = os.environ.get('MXNET_A')\n"
    f1 = lint_source("mxnet_tpu/x.py", src_v1, passes=["env-knob"])
    assert len(f1) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(f1, bl)
    baseline = load_baseline(bl)
    # same findings -> nothing new, even when lines shift
    shifted = lint_source("mxnet_tpu/x.py", "import os\n\n\nA = os.environ.get('MXNET_A')\n",
                          passes=["env-knob"])
    assert apply_baseline(shifted, baseline) == []
    # a second occurrence of the same key -> exactly the surplus is new
    src_v2 = src_v1 + "B = os.environ.get('MXNET_A')\n"
    f2 = lint_source("mxnet_tpu/x.py", src_v2, passes=["env-knob"])
    new = apply_baseline(f2, baseline)
    assert len(new) == 1 and new[0].line == 3


def test_baseline_counts_keys_have_no_line_numbers():
    f = lint_source("mxnet_tpu/x.py", "import os\nA = os.environ.get('X')\n",
                    passes=["env-knob"])
    (key,) = baseline_counts(f)
    assert key.startswith("mxnet_tpu/x.py::env-knob::")
    assert "\n" not in key and ":2:" not in key


# ---------------------------------------------------------------------------
# repo gate + CLI contract
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# perparam-jit
# ---------------------------------------------------------------------------

def test_perparam_jit_immediate_and_cached_dispatch():
    f = lint("""
        import jax
        def apply(params, fns, cache):
            for p in params:
                jax.jit(lambda x: x + 1)(p)
            for k, p in params.items():
                cache._step_cache[k](p)
        """, rule="perparam-jit")
    assert len(f) == 2
    assert all(x.rule == "perparam-jit" for x in f)


def test_perparam_jit_fused_invocation_and_bound_name():
    f = lint("""
        import jax
        def update_all(self, params, g, lr, wd):
            step = jax.jit(lambda w: w - lr * w)
            for w in params:
                self._fused("sgd", None)(w, g, lr, wd)
            for w in params:
                step(w)
        """, rule="perparam-jit")
    assert len(f) == 2


def test_perparam_jit_optimizer_and_kvstore_dispatch():
    f = lint("""
        def update(self, params, grads):
            for i, (w, g) in enumerate(zip(params, grads)):
                self._updater(i, g, w)
            for i, g in enumerate(grads):
                self._kvstore.push(i, g)
                self._kvstore.pull(i, g)
            for i, (w, g) in enumerate(zip(params, grads)):
                self.optimizer.update(i, w, g, None)
        """, rule="perparam-jit")
    assert len(f) == 4


def test_perparam_jit_negative_outside_loop_and_scope():
    # one-shot dispatches and non-loop calls are fine
    f = lint("""
        import jax
        def apply(self, tree, g):
            fn = jax.jit(lambda x: x)
            fn(tree)
            self._updater(0, g, tree)
            self._kvstore.push(0, g)
        """, rule="perparam-jit")
    assert f == []
    # dict/set merges named `opt`/`cfg` are NOT optimizer dispatch
    f = lint("""
        def merge(configs):
            opt = {}
            for cfg in configs:
                opt.update(cfg)
            return opt
        """, rule="perparam-jit")
    assert f == []
    # the pass polices mxnet_tpu/ only (user tools keep their loops)
    f = lint("""
        import jax
        def bench(params):
            for p in params:
                jax.jit(lambda x: x)(p)
        """, rule="perparam-jit", relpath="tools/bench_thing.py")
    assert f == []


def test_gate_repo_is_clean_against_committed_baseline():
    """The acceptance gate: zero non-baselined findings across mxnet_tpu/
    and tools/. A new hazard in a PR lands here as a failure."""
    new, all_findings = lint_paths(["mxnet_tpu", "tools"])
    assert new == [], "new tpulint findings (fix, suppress with justification," \
                      " or --write-baseline):\n" + "\n".join(map(str, new))
    # the baseline itself must stay honest: every entry still matches code
    counts = baseline_counts(all_findings)
    baseline = load_baseline(DEFAULT_BASELINE)
    stale = [k for k in baseline if counts.get(k, 0) < baseline[k]]
    assert stale == [], "stale baseline entries (regenerate with " \
                        "--write-baseline):\n" + "\n".join(stale)


def test_cli_exit_codes(tmp_path):
    clean = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "mxnet_tpu", "tools"],
        cwd=str(REPO), capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    bad = tmp_path / "viol.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    dirty = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", str(bad)],
        cwd=str(REPO), capture_output=True, text=True)
    assert dirty.returncode == 1, dirty.stdout + dirty.stderr
    assert "host-sync" in dirty.stdout


def test_cli_json_format_and_select(tmp_path, capsys):
    bad = tmp_path / "viol.py"
    bad.write_text("import os\ndef f(xs):\n    return [x.asnumpy() for x in xs]\n")
    rc = main([str(bad), "--format", "json", "--select", "host-sync"])
    out = capsys.readouterr().out
    payload = json.loads(out)
    assert rc == 1
    assert payload["total"] == 1 and payload["new"][0]["rule"] == "host-sync"
    # unknown rule -> usage error
    assert main([str(bad), "--select", "no-such-rule"]) == 2


def test_cli_write_baseline_then_clean(tmp_path, capsys):
    bad = tmp_path / "viol.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    bl = tmp_path / "bl.json"
    assert main([str(bad), "--baseline", str(bl), "--write-baseline"]) == 0
    capsys.readouterr()
    assert main([str(bad), "--baseline", str(bl)]) == 0
    # an additional violation beyond the baselined one -> fails again
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n"
                   "def g(xs):\n    return [x.item() for x in xs]\n")
    capsys.readouterr()
    assert main([str(bad), "--baseline", str(bl)]) == 1


def test_collect_files_survives_hidden_ancestor(tmp_path):
    # a dotted ancestor of the scanned dir must not empty the lint scope
    pkg = tmp_path / ".work" / "repo" / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text("x = 1\n")
    (pkg / ".hidden" ).mkdir()
    (pkg / ".hidden" / "skip.py").write_text("x = 1\n")
    files = collect_files([str(pkg)])
    assert [f.name for f in files] == ["mod.py"]


def test_write_baseline_scoped_run_keeps_other_entries(tmp_path, capsys):
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    b.write_text("def g(xs):\n    return [x.item() for x in xs]\n")
    bl = tmp_path / "bl.json"
    assert main([str(a), str(b), "--baseline", str(bl), "--write-baseline"]) == 0
    # re-baselining only a.py must not drop b.py's entry
    assert main([str(a), "--baseline", str(bl), "--write-baseline"]) == 0
    capsys.readouterr()
    assert main([str(a), str(b), "--baseline", str(bl)]) == 0
    # and a scoped *check* of a.py alone must not report b.py's entry stale
    assert main([str(a), "--baseline", str(bl)]) == 0
    assert "stale" not in capsys.readouterr().out


def test_nonexistent_path_is_usage_error(tmp_path, capsys):
    # a typo'd path must not produce a green "0 findings" run
    assert main([str(tmp_path / "does_not_exist.py")]) == 2
    assert main(["mxnet_tpu/no_such_file.py"]) == 2


def test_changed_only_git_failure_is_loud(monkeypatch):
    from tools.tpulint import cli as cli_mod

    monkeypatch.setattr(cli_mod, "changed_files", lambda: None)
    assert cli_mod.main(["--changed-only"]) == 2


def test_changed_only_filter():
    scope = collect_files(["mxnet_tpu"])
    changed = ["mxnet_tpu/base.py", "mxnet_tpu/does_not_exist.py", "README.md"]
    picked = filter_to_scope(changed, scope)
    assert [p.name for p in picked] == ["base.py"]


def test_list_rules_names_all_five(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("host-sync", "tracer-leak", "dtype-drift", "native-guard",
                 "env-knob"):
        assert rule in out


def test_parse_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    found = lint_files([bad], root=tmp_path)
    assert len(found) == 1 and found[0].rule == "parse-error"


def test_undecodable_and_null_byte_files_are_findings_not_crashes(tmp_path):
    latin = tmp_path / "latin.py"
    latin.write_bytes(b"# caf\xe9\nx = 1\n")
    nul = tmp_path / "nul.py"
    nul.write_bytes(b"x = 1\x00\n")
    found = lint_files([latin, nul], root=tmp_path)
    assert sorted(f.rule for f in found) == ["parse-error", "parse-error"]


# ---------------------------------------------------------------------------
# eager-step
# ---------------------------------------------------------------------------

def test_eager_step_gluon_idiom_flagged():
    f = lint("""
        def train(net, loss_fn, trainer, batches):
            for x, y in batches:
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                trainer.step(x.shape[0])
        """, rule="eager-step")
    assert len(f) == 1 and f[0].rule == "eager-step"


def test_eager_step_module_idiom_flagged():
    f = lint("""
        def fit(self, train_data):
            for epoch in range(3):
                for batch in train_data:
                    self.forward_backward(batch)
                    self.update()
        """, rule="eager-step")
    # both the epoch loop and the batch loop contain the full step
    assert len(f) == 2


def test_eager_step_negative_cases():
    # a step outside any loop is a single step, not a loop regime
    f = lint("""
        def one(net, loss_fn, trainer, x, y):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(x.shape[0])
        """, rule="eager-step")
    assert f == []
    # forward-only loops (eval/predict) are fine
    f = lint("""
        def score(net, batches, metric):
            for x, y in batches:
                metric.update(y, net(x))
        """, rule="eager-step")
    assert f == []
    # backward without an update is grad accumulation, not a train step
    f = lint("""
        def grads(net, loss_fn, batches):
            for x, y in batches:
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
        """, rule="eager-step")
    assert f == []
    # ...and metric bookkeeping next to it is still not an optimizer step
    f = lint("""
        def grads(net, loss_fn, batches, eval_metric):
            for x, y in batches:
                with autograd.record():
                    out = net(x)
                    loss = loss_fn(out, y)
                loss.backward()
                eval_metric.update(y, out)
        """, rule="eager-step")
    assert f == []


def test_eager_step_nested_function_not_attributed_to_loop():
    # a step packaged in a closure defined inside a loop body runs when
    # called, not per definition — the loop itself is not flagged
    f = lint("""
        def build(net, loss_fn, trainer, batches):
            fns = []
            for x, y in batches:
                def one_step(x=x, y=y):
                    with autograd.record():
                        loss = loss_fn(net(x), y)
                    loss.backward()
                    trainer.step(1)
                fns.append(one_step)
            return fns
        """, rule="eager-step")
    assert f == []


def test_eager_step_scoped_to_mxnet_tpu():
    src = """
        def train(net, loss_fn, trainer, batches):
            for x, y in batches:
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                trainer.step(1)
    """
    assert lint(src, rule="eager-step",
                relpath="tools/somewhere.py") == []
    assert len(lint(src, rule="eager-step")) == 1


# ---------------------------------------------------------------------------
# decode-host-sync
# ---------------------------------------------------------------------------

def test_decode_host_sync_flags_syncs_in_decode_scope():
    # straight-line code, no loop: the generic host-sync pass is blind
    # here, the cadence comes from the scope name
    f = lint("""
        def decode_step(engine, step):
            sampled = step()
            return fetch_host([sampled])[0]
        """, rule="decode-host-sync")
    assert len(f) == 1 and "fetch_host" in f[0].message

    f = lint("""
        def generate(model, prompt):
            logits = model(prompt)
            return logits.asnumpy()
        """, rule="decode-host-sync")
    assert len(f) == 1 and ".asnumpy" in f[0].message


def test_decode_host_sync_class_scope_and_item():
    # any method of a Decode* class is per-token cadence, whatever its
    # name; .item() and .tolist() are sync calls too
    f = lint("""
        class DecodeEngine:
            def _tick(self):
                tok = self._step()
                return tok.item()
        """, rule="decode-host-sync")
    assert len(f) == 1 and ".item" in f[0].message


def test_decode_host_sync_negative_cases():
    # imdecode (host-side image decoding) must not match the word scope;
    # sync calls outside any decode scope belong to the generic pass
    assert lint("""
        def imdecode(buf):
            return fetch_host([buf])[0]
        """, rule="decode-host-sync") == []
    assert lint("""
        def forward(engine, batch):
            out = engine(batch)
            return fetch_host([out])[0]
        """, rule="decode-host-sync") == []
    # non-sync calls inside decode scope stay clean
    assert lint("""
        def decode_step(engine, toks):
            return engine.step(toks)
        """, rule="decode-host-sync") == []


def test_decode_host_sync_scoped_to_mxnet_tpu():
    src = """
        def decode_loop(step):
            return fetch_host([step()])[0]
    """
    assert lint(src, rule="decode-host-sync",
                relpath="tools/elsewhere.py") == []
    assert len(lint(src, rule="decode-host-sync")) == 1


def test_decode_host_sync_repo_sites_are_baselined():
    # the decode plane keeps exactly its two justified syncs (the tick's
    # sampled-token fetch + the prefill first-token fetch) — baselined,
    # so the repo gate stays clean and any NEW sync is a finding
    counts = load_baseline(DEFAULT_BASELINE)
    key = ("mxnet_tpu/serving/decode.py::decode-host-sync::"
           "`fetch_host()` in decode-plane code runs per token — "
           "a device->host stall every tick")
    assert counts.get(key) == 2


# ---------------------------------------------------------------------------
# replicated-state
# ---------------------------------------------------------------------------

def test_replicated_state_flags_eager_copy_and_device_put():
    f = lint("""
        def restore(updater):
            for i in updater.states:
                updater.states[i] = jnp.copy(updater.states[i])
        """, rule="replicated-state")
    assert len(f) == 1 and "jnp.copy" in f[0].message

    f = lint("""
        def spread(opt_states, repl):
            return [jax.device_put(s, repl) for s in opt_states]
        """, rule="replicated-state")
    assert len(f) == 1 and "device_put" in f[0].message


def test_replicated_state_flags_tree_map_full_tree_copy():
    f = lint("""
        def gather(states, repl):
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, repl), states)
        """, rule="replicated-state")
    assert len(f) == 1 and "tree_map" in f[0].message


def test_replicated_state_negative_cases():
    # non-state arrays stay out of scope
    assert lint("""
        def copy_params(pvals):
            return {n: jnp.copy(v) for n, v in pvals.items()}
        """, rule="replicated-state") == []
    # the blessed layout-aware helpers are the FIX, not a finding
    assert lint("""
        def gather(states, mesh):
            return [parallel.fresh_replicate(s, mesh) for s in states]
        """, rule="replicated-state") == []
    # states_synced is bool bookkeeping, not device state
    assert lint("""
        def mark(updater):
            updater.states_synced = jnp.copy(updater.states_synced)
        """, rule="replicated-state") == []
    # tree_map without a copy/device_put inside is fine
    assert lint("""
        def cast(states):
            return jax.tree_util.tree_map(lambda x: x.astype("f4"), states)
        """, rule="replicated-state") == []


def test_replicated_state_blessed_homes_exempt():
    src = """
        def fresh_replicate(states, repl):
            return jax.device_put(states, repl)
    """
    assert lint(src, rule="replicated-state",
                relpath="mxnet_tpu/parallel.py") == []
    assert lint(src, rule="replicated-state",
                relpath="mxnet_tpu/fastpath/zero.py") == []
    assert lint(src, rule="replicated-state",
                relpath="tools/whatever.py") == []
    assert len(lint(src, rule="replicated-state")) == 1


def test_replicated_state_repo_gate_clean():
    # the repo itself carries ZERO eager state placements — nothing to
    # baseline, and the first regression is a finding
    files = collect_files(["mxnet_tpu"], root=REPO)
    findings = [f for f in lint_files(files, root=REPO,
                                      passes=["replicated-state"])
                if f.rule == "replicated-state"]
    assert findings == []


# ---------------------------------------------------------------------------
# non-atomic-write
# ---------------------------------------------------------------------------

def test_non_atomic_write_flags_bare_open_on_ckpt_path():
    f = lint("""
        def store(ckpt_path, blob):
            with open(ckpt_path, "wb") as fh:
                fh.write(blob)
        """, rule="non-atomic-write")
    assert len(f) == 1 and "open" in f[0].message
    # checkpoint-ish by FUNCTION even when the path arg is opaque
    f = lint("""
        def save_states(fname, blob):
            open(fname, "wb").write(blob)
        """, rule="non-atomic-write")
    assert len(f) == 1


def test_non_atomic_write_flags_np_save_and_pickle_dump():
    f = lint("""
        def snapshot(path, arr):
            np.save(path, arr)
        """, rule="non-atomic-write")
    assert len(f) == 1 and "np.save" in f[0].message
    f = lint("""
        def write(obj, manifest_file):
            pickle.dump(obj, manifest_file)
        """, rule="non-atomic-write")
    assert len(f) == 1 and "pickle.dump" in f[0].message


def test_non_atomic_write_negative_cases():
    # reads are fine, and writes to non-checkpoint paths are out of scope
    assert lint("""
        def load(ckpt_path):
            with open(ckpt_path, "rb") as fh:
                return fh.read()
        """, rule="non-atomic-write") == []
    assert lint("""
        def emit(log_path, line):
            open(log_path, "a").write(line)
        """, rule="non-atomic-write") == []
    # tools/tests are out of scope — only mxnet_tpu/ carries the contract
    assert lint("""
        def save(ckpt_path, blob):
            open(ckpt_path, "wb").write(blob)
        """, rule="non-atomic-write", relpath="tools/whatever.py") == []


def test_non_atomic_write_commit_helpers_exempt():
    # the atomic helpers themselves, and writer lambdas routed through
    # them, ARE the sanctioned implementation
    assert lint("""
        def _atomic_write(path, writer):
            tmp = path + ".tmp"
            with open(tmp, "wb") as fh:
                fh.write(b"checkpoint")
            os.replace(tmp, path)
        """, rule="non-atomic-write") == []
    assert lint("""
        def save(self, epoch, blob):
            self._commit(self._params_path(epoch),
                         lambda p: open(p, "wb").write(blob))
        """, rule="non-atomic-write") == []
    assert lint("""
        def save(self, epoch, blob):
            self._commit_bytes(self._shard_path(epoch), blob, "shard")
        """, rule="non-atomic-write") == []


def test_non_atomic_write_repo_gate_clean():
    # every pre-existing bare write rides the committed baseline; the
    # elastic checkpoint plane itself must be finding-free
    files = collect_files(["mxnet_tpu"], root=REPO)
    findings = [f for f in lint_files(files, root=REPO,
                                      passes=["non-atomic-write"])]
    baseline = load_baseline(DEFAULT_BASELINE)
    assert apply_baseline(findings, baseline) == []
    assert [f for f in findings if "elastic" in f.path] == []


# ---------------------------------------------------------------------------
# unbounded-queue
# ---------------------------------------------------------------------------

def test_unbounded_queue_flags_bare_queue():
    f = lint("""
        def start(ctx):
            tasks = queue.Queue()
            return tasks
        """, rule="unbounded-queue")
    assert len(f) == 1 and "queue.Queue" in f[0].message
    # multiprocessing / context spellings and attribute targets count too
    f = lint("""
        class P:
            def __init__(self, ctx):
                self._task_q = ctx.Queue()
        """, rule="unbounded-queue")
    assert len(f) == 1


def test_unbounded_queue_flags_queueish_deque():
    f = lint("""
        class Server:
            def __init__(self):
                self._queue = collections.deque()
        """, rule="unbounded-queue")
    assert len(f) == 1 and "maxlen" in f[0].message
    # a literal maxlen=None is spelled-out unboundedness, not a bound
    f = lint("""
        def make():
            req_queue = deque(maxlen=None)
            return req_queue
        """, rule="unbounded-queue")
    assert len(f) == 1
    # subscript target: per-tenant sub-queue dicts are still queues
    f = lint("""
        def add(self, tid):
            self._queues[tid] = collections.deque()
        """, rule="unbounded-queue")
    assert len(f) == 1


def test_unbounded_queue_negative_cases():
    # bounded constructions are the fix, not a finding
    assert lint("""
        def start(self, depth):
            self._queue = queue.Queue(maxsize=depth)
            self._q2 = queue.Queue(depth)
        """, rule="unbounded-queue") == []
    assert lint("""
        class T:
            def __init__(self, depth):
                self.queue = collections.deque(maxlen=depth)
        """, rule="unbounded-queue") == []
    # a deque that is NOT queue-named is a general container — out of
    # scope (flagging every deque would bury the signal)
    assert lint("""
        def collect():
            pending = collections.deque()
            history = deque()
            return pending, history
        """, rule="unbounded-queue") == []


def test_unbounded_queue_scope_is_mxnet_tpu():
    src = """
        def start():
            tasks = queue.Queue()
            return tasks
    """
    assert lint(src, rule="unbounded-queue",
                relpath="tools/whatever.py") == []
    assert lint(src, rule="unbounded-queue",
                relpath="tests/test_x.py") == []
    assert len(lint(src, rule="unbounded-queue")) == 1


def test_unbounded_queue_repo_gate_clean_and_justified():
    # the serving planes (batcher, decode, tenancy sub-queues) are
    # bounded by construction — finding-free; the two multiprocessing
    # image-pipeline queues ride the baseline WITH a justification
    files = collect_files(["mxnet_tpu"], root=REPO)
    findings = [f for f in lint_files(files, root=REPO,
                                      passes=["unbounded-queue"])]
    assert [f for f in findings if "serving" in f.path] == []
    baseline = load_baseline(DEFAULT_BASELINE)
    assert apply_baseline(findings, baseline) == []
    justs = core.load_justifications(DEFAULT_BASELINE)
    for f in findings:
        assert f.baseline_key() in justs, \
            "unbounded-queue baseline entries must carry a justification"


# ---------------------------------------------------------------------------
# metric-cardinality
# ---------------------------------------------------------------------------

def test_metric_cardinality_flags_interpolated_labels():
    # f-string, %-format and .format label values are runtime data
    f = lint("""
        REQS = telemetry.counter("mxnet_x_total", labels=("rid",))
        def f(request_id):
            REQS.inc(rid=f"req-{request_id}")
        """, rule="metric-cardinality")
    assert len(f) == 1 and "'rid'" in f[0].message
    f = lint("""
        REQS = telemetry.counter("mxnet_x_total", labels=("who",))
        def f(uid):
            REQS.inc(who="user-%s" % uid)
        """, rule="metric-cardinality")
    assert len(f) == 1
    f = lint("""
        H = telemetry.histogram("mxnet_h_ms", labels=("k",))
        def f(x, ms):
            H.observe(ms, k="{}".format(x))
        """, rule="metric-cardinality")
    assert len(f) == 1


def test_metric_cardinality_flags_exception_text_and_ids():
    # str(e) / a bare except-handler binding IS exception text; id-ish
    # parameter names (request_id, trace_id, prompt) are per-request data
    f = lint("""
        G = telemetry.gauge("mxnet_g", labels=("err",))
        def f():
            try:
                pass
            except Exception as e:
                G.set(1, err=str(e))
        """, rule="metric-cardinality")
    assert len(f) == 1 and "str()" in f[0].message
    f = lint("""
        G = telemetry.gauge("mxnet_g", labels=("err",))
        def f():
            try:
                pass
            except Exception as e:
                G.set(1, err=e)
        """, rule="metric-cardinality")
    assert len(f) == 1
    f = lint("""
        H = telemetry.histogram("mxnet_h_ms", labels=("req",))
        def f(trace_id, ms):
            H.observe(ms, req=trace_id)
        """, rule="metric-cardinality")
    assert len(f) == 1


def test_metric_cardinality_sees_chained_and_cross_module_handles():
    # telemetry.counter(...).inc(...) and the ALL-CAPS cross-module
    # handle convention (telemetry.RECOMPILES) are both update sites
    f = lint("""
        def f(prompt):
            telemetry.counter("mxnet_p_total", labels=("p",)).inc(p=prompt)
        """, rule="metric-cardinality")
    assert len(f) == 1
    f = lint("""
        from .. import telemetry
        def f(request_id):
            telemetry.RECOMPILES.inc(site="x-%s" % request_id)
        """, rule="metric-cardinality")
    assert len(f) == 1


def test_metric_cardinality_negative_cases():
    # constant labels, plain bounded names, attribute reads and the
    # tenant exemption (TenantRegistry bounds tenant ids) are all legal
    assert lint("""
        T = telemetry.counter("mxnet_t", labels=("event",))
        def f():
            T.inc(event="shed")
        """, rule="metric-cardinality") == []
    assert lint("""
        T = telemetry.counter("mxnet_t", labels=("tenant",))
        def f(tenant_id):
            T.inc(tenant="t-%s" % tenant_id)
        """, rule="metric-cardinality") == []
    assert lint("""
        T = telemetry.counter("mxnet_t", labels=("site",))
        def f(site):
            T.inc(site=site)
        """, rule="metric-cardinality") == []
    assert lint("""
        T = telemetry.gauge("mxnet_g", labels=("server",))
        class S:
            def f(self):
                T.set(1, server=self.name)
        """, rule="metric-cardinality") == []
    # a non-metric receiver's .set() is out of scope
    assert lint("""
        def f(x, request_id):
            x.set(1, rid=request_id)
        """, rule="metric-cardinality") == []
    # scope is mxnet_tpu/ only
    assert lint("""
        T = telemetry.counter("t", labels=("rid",))
        def f(request_id):
            T.inc(rid=f"{request_id}")
        """, rule="metric-cardinality",
        relpath="tools/whatever.py") == []


def test_metric_cardinality_repo_gate_clean_and_justified():
    # survivors (PJRT device ordinals, exception CLASS names) ride the
    # baseline WITH a justification each; everything else is clean
    files = collect_files(["mxnet_tpu"], root=REPO)
    findings = [f for f in lint_files(files, root=REPO,
                                      passes=["metric-cardinality"])]
    baseline = load_baseline(DEFAULT_BASELINE)
    assert apply_baseline(findings, baseline) == []
    justs = core.load_justifications(DEFAULT_BASELINE)
    for f in findings:
        assert f.baseline_key() in justs, \
            "metric-cardinality baseline entries must carry a justification"
    # the new telemetry v2 modules are finding-free by construction
    assert [f for f in findings
            if "tracing" in f.path or "flightrec" in f.path
            or "slo" in f.path or "httpd" in f.path] == []


# ---------------------------------------------------------------------------
# whole-program graph engine (symbol table / call graph / lattices)
# ---------------------------------------------------------------------------

from tools.tpulint import graph as graph_mod  # noqa: E402
from tools.tpulint.core import FileContext, lint_sources  # noqa: E402


def make_graph(files, depth=graph_mod.DEFAULT_DEPTH):
    """Build a ProjectGraph over {relpath: source} fixtures."""
    ctxs = [FileContext(rp, textwrap.dedent(src), filename=rp)
            for rp, src in sorted(files.items())]
    return graph_mod.build_graph([(c.relpath, c.tree) for c in ctxs],
                                 depth=depth)


def fn_of(gph, qname):
    for info in gph.funcs.values():
        if info.qname == qname:
            return info
    raise AssertionError("no function %r in graph (have: %s)"
                         % (qname, sorted(i.qname for i in gph.funcs.values())))


def test_graph_aliased_import_call_edges():
    gph = make_graph({
        "mxnet_tpu/a.py": """
            def helper(x):
                return x + 1
        """,
        "mxnet_tpu/b.py": """
            from mxnet_tpu.a import helper as h2
            import mxnet_tpu.a as amod
            from .a import helper as h3

            def via_from_alias(x):
                return h2(x)

            def via_module_alias(x):
                return amod.helper(x)

            def via_relative(x):
                return h3(x)
        """})
    helper = fn_of(gph, "mxnet_tpu/a.py::helper")
    for caller in ("via_from_alias", "via_module_alias", "via_relative"):
        info = fn_of(gph, "mxnet_tpu/b.py::%s" % caller)
        assert helper in info.callees, caller


def test_graph_package_init_reexport_resolves():
    # `from .mod import helper` inside pkg/__init__.py resolves against
    # pkg itself (not one level up), so re-export chains through package
    # __init__ files keep their call edges — the mxnet_tpu subpackages
    # (fastpath, serving, telemetry) all re-export this way
    gph = make_graph({
        "pkg/__init__.py": """
            from .mod import helper
        """,
        "pkg/mod.py": """
            def helper(x):
                return x.asnumpy()
        """,
        "pkg/use.py": """
            import jax
            from pkg import helper

            @jax.jit
            def step(x):
                return helper(x)
        """})
    helper = fn_of(gph, "pkg/mod.py::helper")
    step = fn_of(gph, "pkg/use.py::step")
    assert helper in step.callees
    assert gph.is_traced(helper.node)


def test_graph_method_binding_self_and_base_class():
    gph = make_graph({
        "mxnet_tpu/base_mod.py": """
            class Base:
                def shared(self):
                    return 1
        """,
        "mxnet_tpu/impl.py": """
            from mxnet_tpu.base_mod import Base

            class Impl(Base):
                def own(self):
                    return 2

                def caller(self):
                    return self.own() + self.shared() + Impl.own(self)
        """})
    caller = fn_of(gph, "mxnet_tpu/impl.py::Impl.caller")
    own = fn_of(gph, "mxnet_tpu/impl.py::Impl.own")
    shared = fn_of(gph, "mxnet_tpu/base_mod.py::Base.shared")
    assert own in caller.callees          # self-binding (and Class.method)
    assert shared in caller.callees       # base-class binding by name


def test_graph_decorated_functions_still_resolve():
    gph = make_graph({
        "mxnet_tpu/d.py": """
            import functools

            def deco(fn):
                return fn

            @deco
            def decorated(x):
                return x

            def caller(x):
                return decorated(x)
        """})
    assert fn_of(gph, "mxnet_tpu/d.py::decorated") in \
        fn_of(gph, "mxnet_tpu/d.py::caller").callees


def test_graph_recursion_terminates_and_depth_cutoff():
    # direct + mutual recursion must terminate; a chain longer than the
    # propagation bound is cut off at DEFAULT_DEPTH frames from the seed.
    # (Seeded via the graph-only `_leaf_step` name seed: the same-file
    # jit closure in `core.jit_functions` is deliberately unbounded.)
    depth = graph_mod.DEFAULT_DEPTH
    n = depth + 2
    chain = "\n".join(
        "def f%d(x):\n    return f%d(x)" % (i, i + 1) for i in range(n))
    src = """
        import jax

        def rec(x):
            return rec(x)

        def _leaf_step(x):
            return f0(x)

        %s

        def f%d(x):
            return x

        jax.jit(rec)
    """ % (chain.replace("\n", "\n        "), n)
    gph = make_graph({"mxnet_tpu/r.py": src})
    assert gph.is_traced(fn_of(gph, "mxnet_tpu/r.py::rec").node)
    # fk sits at distance k+1 from the seed: within the bound traced,
    # beyond it cut off
    assert gph.is_traced(fn_of(gph, "mxnet_tpu/r.py::f%d" % (depth - 1)).node)
    assert not gph.is_traced(fn_of(gph, "mxnet_tpu/r.py::f%d" % depth).node)
    assert not gph.is_traced(fn_of(gph, "mxnet_tpu/r.py::f%d" % n).node)


def test_graph_traced_lattice_seeds_and_chain():
    gph = make_graph({
        "mxnet_tpu/opt.py": """
            class SGD:
                def _leaf_step(self, w, g):
                    return self._clip(w - g)

                def _clip(self, x):
                    return x
        """,
        "mxnet_tpu/plane.py": """
            import jax

            class Plane:
                def _build_step(self):
                    def step(x):
                        return helper(x)
                    return step

                def activate(self):
                    self._fn = jax.jit(self._build_step())

            def helper(x):
                return x
        """})
    clip = fn_of(gph, "mxnet_tpu/opt.py::SGD._clip")
    assert gph.is_traced(clip.node)                 # seeded at _leaf_step
    assert gph.traced_chain(clip.node) == ["SGD._leaf_step", "SGD._clip"]
    # factory-returned nested function + its callees are traced
    step = fn_of(gph, "mxnet_tpu/plane.py::Plane._build_step.step")
    helper = fn_of(gph, "mxnet_tpu/plane.py::helper")
    assert gph.is_traced(step.node) and gph.is_traced(helper.node)


def test_graph_thread_lattice_seeds():
    gph = make_graph({
        "mxnet_tpu/w.py": """
            import threading

            class Emitter(threading.Thread):
                def run(self):
                    self.emit()

                def emit(self):
                    pass

            class Server:
                def start(self):
                    self._t = threading.Thread(target=self._worker)

                def _worker(self):
                    helper()

            class Saver:
                def save(self):
                    def commit():
                        finish()
                    self._engine.push(commit)

            def helper():
                pass

            def finish():
                pass

            def main_only():
                helper()
        """})
    for q in ("Emitter.run", "Emitter.emit", "Server._worker", "helper",
              "Saver.save.commit", "finish"):
        assert gph.is_threaded(fn_of(gph, "mxnet_tpu/w.py::%s" % q).node), q
    assert not gph.is_threaded(fn_of(gph, "mxnet_tpu/w.py::main_only").node)
    assert gph.thread_entry(
        fn_of(gph, "mxnet_tpu/w.py::Server._worker").node) == "Server._worker"


# ---------------------------------------------------------------------------
# traced-host-sync
# ---------------------------------------------------------------------------

def test_traced_host_sync_two_calls_below_leaf_step():
    f = lint("""
        def _leaf_step(w, g):
            return _apply(w, g)

        def _apply(w, g):
            return _norm(w - g)

        def _norm(x):
            return x / float(x.sum())
    """, "traced-host-sync")
    assert len(f) == 1
    assert "float()" in f[0].message and "_leaf_step" in f[0].message
    assert "_norm" in f[0].message


def test_traced_host_sync_cross_file_jit_reachability():
    found = lint_sources([
        ("mxnet_tpu/helpers.py", textwrap.dedent("""
            def helper(x):
                return x.asnumpy()
        """)),
        ("mxnet_tpu/steps.py", textwrap.dedent("""
            import jax
            from mxnet_tpu.helpers import helper

            @jax.jit
            def step(x):
                return helper(x)
        """)),
    ], passes=["traced-host-sync"])
    assert len(found) == 1 and found[0].path == "mxnet_tpu/helpers.py"
    assert ".asnumpy()" in found[0].message


def test_traced_host_sync_flags_get_env_and_locks():
    f = lint("""
        def _leaf_step(w):
            knob = get_env("MXNET_X", 0, int, cache=False)
            with self._lock:
                w = w + knob
            self._mu.acquire()
            return w
    """, "traced-host-sync")
    msgs = " ".join(x.message for x in f)
    assert len(f) == 3
    assert "get_env(cache=False)" in msgs and "lock" in msgs


def test_traced_host_sync_negative_and_no_double_report():
    # not reachable from any traced seed -> clean
    assert lint("""
        def host_loop(xs):
            return xs[0].asnumpy()
    """, "traced-host-sync") == []
    # lexically inside a same-file jit closure: host-sync owns the report
    src = """
        import jax

        @jax.jit
        def step(x):
            return x.item()
    """
    assert lint(src, "traced-host-sync") == []
    assert len(lint(src, "host-sync")) == 1


def test_traced_host_sync_scoped_to_mxnet_tpu():
    src = """
        def _leaf_step(w):
            return float(w.sum())
    """
    assert lint(src, "traced-host-sync", relpath="tools/x.py") == []
    assert len(lint(src, "traced-host-sync")) == 1


# ---------------------------------------------------------------------------
# use-after-donate
# ---------------------------------------------------------------------------

def test_use_after_donate_read_after_fused_apply():
    f = lint("""
        def apply(opt, idx, grads, weights, states):
            new_w, new_s = fused_apply(opt, idx, grads, weights, states)
            return weights[0], new_w
    """, "use-after-donate")
    assert len(f) == 1 and "`weights`" in f[0].message


def test_use_after_donate_rebind_and_invalidate_clear():
    assert lint("""
        def rebound(opt, idx, g, weights, states):
            weights = fused_apply(opt, idx, g, weights, states)
            return weights
    """, "use-after-donate") == []
    assert lint("""
        def disciplined(opt, idx, g, weights, states):
            new_w = fused_apply(opt, idx, g, weights, states)
            invalidate_consumed(consumed, (new_w,))
            return weights
    """, "use-after-donate") == []


def test_use_after_donate_donation_prep_window_opens_at_consumer():
    # reads between prep and the consuming jit are the sanctioned pattern
    assert lint("""
        def ok(flat_ws, buckets, fn):
            argnums, consumed = donation_prep(flat_ws, buckets)
            new_ws, new_buckets = fn(flat_ws, buckets)
            buckets = new_buckets
            return new_ws
    """, "use-after-donate") == []
    # ...but a read AFTER the consumer is stale
    f = lint("""
        def stale(flat_ws, buckets, fn):
            argnums, consumed = donation_prep(flat_ws, buckets)
            new_ws = fn(flat_ws, buckets)
            return flat_ws[0]
    """, "use-after-donate")
    assert len(f) == 1 and "`flat_ws`" in f[0].message


def test_use_after_donate_local_donating_jit_and_self_attr():
    f = lint("""
        import jax

        def local_jit(pools, x):
            step = jax.jit(kernel, donate_argnums=(0,))
            out = step(pools, x)
            return pools[0]
    """, "use-after-donate")
    assert len(f) == 1 and "`pools`" in f[0].message
    # the decode pattern: a donating jit installed in __init__, the pool
    # donated in another method, rebound from the jit's outputs -> clean
    assert lint("""
        import jax

        class Engine:
            def __init__(self):
                self._step = jax.jit(kernel, donate_argnums=(0,))

            def tick(self, x):
                out, pools = self._step(self._pools, x)
                self._pools = pools
                return out
    """, "use-after-donate") == []
    # ...without the rebind, the next read is stale
    f = lint("""
        import jax

        class Engine:
            def __init__(self):
                self._step = jax.jit(kernel, donate_argnums=(0,))

            def tick(self, x):
                out = self._step(self._pools, x)
                return self._pools
    """, "use-after-donate")
    assert len(f) == 1 and "self._pools" in f[0].message


def test_use_after_donate_fused_py_is_exempt():
    src = """
        def probe(weights):
            new = fused_apply(None, None, None, weights, None)
            return weights
    """
    assert lint(src, "use-after-donate",
                relpath="mxnet_tpu/fastpath/fused.py") == []
    assert len(lint(src, "use-after-donate")) == 1


# ---------------------------------------------------------------------------
# shared-state-race
# ---------------------------------------------------------------------------

def test_shared_state_race_unlocked_cross_thread_write():
    f = lint("""
        import threading

        class W:
            def __init__(self):
                self._n = 0
                self._t = threading.Thread(target=self._run)

            def _run(self):
                self._n += 1

            def snapshot(self):
                return self._n
    """, "shared-state-race")
    assert len(f) == 1
    assert "`self._n`" in f[0].message and "W.snapshot" in f[0].message


def test_shared_state_race_common_lock_is_clean():
    assert lint("""
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._t = threading.Thread(target=self._run)

            def _run(self):
                with self._lock:
                    self._n += 1

            def snapshot(self):
                with self._lock:
                    return self._n
    """, "shared-state-race") == []


def test_shared_state_race_one_sided_lock_still_flagged():
    f = lint("""
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._t = threading.Thread(target=self._run)

            def _run(self):
                with self._lock:
                    self._n += 1

            def snapshot(self):
                return self._n
    """, "shared-state-race")
    assert len(f) == 1


def test_shared_state_race_init_exemptions():
    # writes in __init__ are pre-start() on either side — including an
    # object CONSTRUCTED on the worker thread (publication via queue)
    assert lint("""
        import threading

        class Batch:
            def __init__(self, data):
                self.data = data

            def __str__(self):
                return str(self.data)

        class W:
            def __init__(self):
                self._t = threading.Thread(target=self._run)

            def _run(self):
                b = Batch([1])
                self._q.put(b)
    """, "shared-state-race") == []


def test_shared_state_race_worker_closure_in_init_is_thread_context():
    # a closure defined in __init__ but handed to Thread(target=...) runs
    # on the worker — its writes do NOT get the construction exemption
    f = lint("""
        import threading

        class W:
            def __init__(self):
                def worker():
                    self._state = 1
                self._t = threading.Thread(target=worker)

            def peek(self):
                return self._state
    """, "shared-state-race")
    assert len(f) == 1 and "`self._state`" in f[0].message


def test_shared_state_race_scoped_to_mxnet_tpu():
    src = """
        import threading

        class W:
            def __init__(self):
                self._n = 0
                self._t = threading.Thread(target=self._run)

            def _run(self):
                self._n += 1

            def peek(self):
                return self._n
    """
    assert lint(src, "shared-state-race", relpath="tools/x.py") == []
    assert len(lint(src, "shared-state-race")) == 1


def test_shared_state_race_repo_findings_are_baselined_with_justifications():
    # every baselined interprocedural finding must carry a one-line
    # justification (the acceptance contract for the whole-program gate)
    counts = load_baseline(DEFAULT_BASELINE)
    justs = core.load_justifications(DEFAULT_BASELINE)
    race_keys = [k for k in counts if "::shared-state-race::" in k]
    assert race_keys, "expected the known worker-counter findings baselined"
    for k in race_keys:
        assert justs.get(k), "baselined finding lacks a justification: %s" % k


# ---------------------------------------------------------------------------
# seeded synthetic bugs (fixture module): each pass catches exactly its bug
# ---------------------------------------------------------------------------

SEEDED = (REPO / "tests" / "fixtures" / "tpulint_seeded_bugs.py").read_text()


def _lint_seeded(rule):
    # linted under a mxnet_tpu/ pseudo-path: the passes police the
    # framework package only
    return lint_source("mxnet_tpu/_seeded_bugs.py", SEEDED, passes=[rule])


def test_seeded_bug_traced_host_sync():
    f = _lint_seeded("traced-host-sync")
    assert len(f) == 1
    assert "float()" in f[0].message and "_leaf_step" in f[0].message


def test_seeded_bug_use_after_donate():
    f = _lint_seeded("use-after-donate")
    assert len(f) == 1 and "`weights`" in f[0].message


def test_seeded_bug_shared_state_race():
    f = _lint_seeded("shared-state-race")
    assert len(f) == 1 and "`self._count`" in f[0].message


def test_seeded_bugs_exactly_three_across_all_passes():
    f = lint_source("mxnet_tpu/_seeded_bugs.py", SEEDED)
    assert sorted(x.rule for x in f) == \
        ["shared-state-race", "traced-host-sync", "use-after-donate"]


# ---------------------------------------------------------------------------
# v4 concurrency passes: lock-order-cycle / blocking-under-lock /
# cv-protocol / resource-lifecycle (tools/tpulint/locks.py)
# ---------------------------------------------------------------------------

LOCK_RULES = ["blocking-under-lock", "cv-protocol", "lock-order-cycle",
              "resource-lifecycle"]
LOCK_BUGS = (REPO / "tests" / "fixtures" / "tpulint_lock_bugs.py").read_text()
LOCK_CLEAN = (REPO / "tests" / "fixtures"
              / "tpulint_lock_clean.py").read_text()


def _lint_lock_bugs(rule):
    return lint_source("mxnet_tpu/_lock_bugs.py", LOCK_BUGS, passes=[rule])


def test_lock_bug_lock_order_cycle():
    f = _lint_lock_bugs("lock-order-cycle")
    assert len(f) == 1
    assert "PoolA._lock" in f[0].message and "PoolB._lock" in f[0].message
    # both witness directions are named
    assert "PoolA.forward" in f[0].message
    assert "PoolB.backward" in f[0].message


def test_lock_bug_blocking_under_lock():
    f = _lint_lock_bugs("blocking-under-lock")
    assert len(f) == 1
    assert "fetch_host" in f[0].message and "Sampler._lock" in f[0].message


def test_lock_bug_cv_protocol():
    f = _lint_lock_bugs("cv-protocol")
    assert len(f) == 1
    assert "bare" in f[0].message and "while" in f[0].message


def test_lock_bug_resource_lifecycle():
    f = _lint_lock_bugs("resource-lifecycle")
    assert len(f) == 1
    assert "reserve" in f[0].message and "KV cache pages" in f[0].message


def test_lock_bugs_exactly_four_across_all_passes():
    # each seeded bug is caught by EXACTLY its pass — no cross-talk with
    # any other pass in the registry
    f = lint_source("mxnet_tpu/_lock_bugs.py", LOCK_BUGS)
    assert sorted(x.rule for x in f) == LOCK_RULES


def test_lock_clean_fixture_zero_findings_across_all_passes():
    # the tick-boundary swap, caller-protection, subscript-store transfer
    # and lifecycle-synchronized hand-off idioms must never be flagged —
    # by ANY pass, not just the four new ones
    f = lint_source("mxnet_tpu/_lock_clean.py", LOCK_CLEAN)
    assert f == []


def test_lock_order_one_way_hierarchy_is_clean():
    # a strict A->B ordering (the repo's engine->tenant shape) is fine;
    # only a cycle deadlocks
    src = """
        import threading

        class Outer:
            def __init__(self, inner: "Inner"):
                self._lock = threading.Lock()
                self.inner = inner

            def step(self):
                with self._lock:
                    return self.inner.poke()

        class Inner:
            def __init__(self):
                self._lock = threading.Lock()

            def poke(self):
                with self._lock:
                    return 1
    """
    assert lint(src, "lock-order-cycle") == []


def test_blocking_under_lock_transitive_names_witness_chain():
    src = """
        import threading

        class Holder:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    return self._drain()

            def _drain(self):
                import time
                time.sleep(0.1)
    """
    f = lint(src, "blocking-under-lock")
    assert len(f) == 1
    assert "time.sleep" in f[0].message and "_drain" in f[0].message


def test_blocking_under_lock_str_join_and_timed_get_are_clean():
    src = """
        import threading

        class Holder:
            def __init__(self, q):
                self._lock = threading.Lock()
                self._q = q

            def fmt(self, xs):
                with self._lock:
                    item = self._q.get(timeout=0.5)
                    return ", ".join(str(x) for x in xs) + str(item)
    """
    assert lint(src, "blocking-under-lock") == []


def test_blocking_under_lock_untimed_queue_get_flagged():
    src = """
        import threading

        class Holder:
            def __init__(self, q):
                self._lock = threading.Lock()
                self._q = q

            def pull(self):
                with self._lock:
                    return self._q.get()
    """
    f = lint(src, "blocking-under-lock")
    assert len(f) == 1 and "queue.get()" in f[0].message


def test_cv_protocol_untimed_wait_without_shutdown_flag():
    src = """
        import threading

        class W:
            def __init__(self):
                self._cv = threading.Condition()
                self._items = []

            def pull(self):
                with self._cv:
                    while not self._items:
                        self._cv.wait()
                    return self._items.pop()
    """
    f = lint(src, "cv-protocol")
    assert len(f) == 1 and "shutdown" in f[0].message


def test_cv_protocol_timed_looped_shutdown_wait_is_clean():
    src = """
        import threading

        class W:
            def __init__(self):
                self._cv = threading.Condition()
                self._items = []
                self._closed = False

            def pull(self):
                with self._cv:
                    while not self._items and not self._closed:
                        self._cv.wait(0.5)
                    self._cv.notify_all()
    """
    assert lint(src, "cv-protocol") == []


def test_cv_protocol_notify_without_cv_lock():
    src = """
        import threading

        class W:
            def __init__(self):
                self._cv = threading.Condition()

            def kick(self):
                self._cv.notify_all()
    """
    f = lint(src, "cv-protocol")
    assert len(f) == 1 and "notify" in f[0].message


def test_resource_lifecycle_try_finally_is_clean():
    src = """
        class C:
            def __init__(self, cache):
                self._cache = cache

            def run(self, slot, pages):
                self._cache.reserve(slot, pages)
                try:
                    return self._work(slot)
                finally:
                    self._cache.free(slot)

            def _work(self, slot):
                return slot
    """
    assert lint(src, "resource-lifecycle") == []


def test_resource_lifecycle_early_return_leak():
    src = """
        class C:
            def __init__(self, cache):
                self._cache = cache

            def run(self, slot, pages, fast):
                self._cache.reserve(slot, pages)
                if fast:
                    return None
                self._cache.free(slot)
    """
    f = lint(src, "resource-lifecycle")
    assert len(f) == 1 and "return" in f[0].message


def test_lock_rule_repo_findings_are_baselined_with_justifications():
    # same acceptance contract as shared-state-race: every baselined
    # finding from the four concurrency passes carries a justification
    counts = load_baseline(DEFAULT_BASELINE)
    justs = core.load_justifications(DEFAULT_BASELINE)
    keys = [k for k in counts
            if any("::%s::" % r in k for r in LOCK_RULES)]
    # the deliberate admission-guard hand-offs are known and must stay
    # documented
    assert any("::resource-lifecycle::" in k for k in keys), \
        "expected the admission-guard hand-off findings baselined"
    for k in keys:
        assert justs.get(k), "baselined finding lacks a justification: %s" % k


# ---------------------------------------------------------------------------
# incremental cache + --stats + runtime gates
# ---------------------------------------------------------------------------

from tools.tpulint.cache import LintCache  # noqa: E402


def test_cache_warm_hits_and_identical_findings(tmp_path):
    a = tmp_path / "a.py"
    a.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    cache1 = LintCache(tmp_path / "c.json")
    cold = lint_files([a], root=tmp_path, cache=cache1)
    assert cache1.hits == 0 and cache1.misses > 0
    cache2 = LintCache(tmp_path / "c.json")
    warm = lint_files([a], root=tmp_path, cache=cache2)
    assert cache2.misses == 0 and cache2.hits > 0
    assert [str(f) for f in warm] == [str(f) for f in cold]


def test_cache_invalidated_by_edit_and_scope_change(tmp_path):
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    b.write_text("X = 1\n")
    path = tmp_path / "c.json"
    lint_files([a, b], root=tmp_path, cache=LintCache(path))

    # editing b: a's LOCAL results stay cached, project results (keyed by
    # the scope signature) re-run for everyone
    b.write_text("X = 2\n")
    c = LintCache(path)
    stats = {}
    lint_files([a, b], root=tmp_path, cache=c, stats=stats)
    assert c.hits > 0 and c.misses > 0
    from tools.tpulint.core import all_passes
    n_project = sum(1 for p in all_passes().values() if p.project)
    # both files re-run every project pass; only b re-runs local passes
    assert c.misses >= 2 * n_project

    # unchanged again -> full hit, and no pass executed at all
    c2 = LintCache(path)
    stats2 = {}
    lint_files([a, b], root=tmp_path, cache=c2, stats=stats2)
    assert c2.misses == 0 and stats2["pass_ms"] == {}


def test_cache_findings_survive_roundtrip_suppressed(tmp_path):
    # suppressions live in the hashed content: cached results honor them
    a = tmp_path / "a.py"
    a.write_text("def f(xs):\n"
                 "    return [x.asnumpy() for x in xs]"
                 "  # tpulint: disable=host-sync\n")
    path = tmp_path / "c.json"
    assert lint_files([a], root=tmp_path, cache=LintCache(path),
                      passes=["host-sync"]) == []
    assert lint_files([a], root=tmp_path, cache=LintCache(path),
                      passes=["host-sync"]) == []


def test_cli_stats_flag(tmp_path, capsys):
    bad = tmp_path / "v.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    rc = main([str(bad), "--stats", "--format", "json",
               "--cache", str(tmp_path / "c.json")])
    captured = capsys.readouterr()
    assert rc == 1
    # stats go to stderr so --format json keeps a parseable stdout
    json.loads(captured.out)
    assert "tpulint --stats:" in captured.err and "cache:" in captured.err \
        and "pass " in captured.err and "total:" in captured.err


def test_runtime_gate_cold_under_30s_warm_under_5s(tmp_path):
    """The tier-1 cost contract for the whole-program engine: a cold run
    over mxnet_tpu/ completes in under 30s, a warm (fully cached) run in
    under 5s."""
    import time

    cache = str(tmp_path / "gate-cache.json")
    t0 = time.monotonic()
    cold = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "mxnet_tpu",
         "--cache", cache],
        cwd=str(REPO), capture_output=True, text=True)
    cold_s = time.monotonic() - t0
    assert cold.returncode == 0, cold.stdout + cold.stderr
    assert cold_s < 30.0, "cold whole-program lint took %.1fs" % cold_s

    t0 = time.monotonic()
    warm = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "mxnet_tpu",
         "--cache", cache],
        cwd=str(REPO), capture_output=True, text=True)
    warm_s = time.monotonic() - t0
    assert warm.returncode == 0, warm.stdout + warm.stderr
    assert warm_s < 5.0, "warm (cached) lint took %.1fs" % warm_s


def test_write_baseline_preserves_justifications(tmp_path, capsys):
    bad = tmp_path / "v.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    bl = tmp_path / "bl.json"
    assert main([str(bad), "--baseline", str(bl), "--write-baseline",
                 "--cache", str(tmp_path / "c.json")]) == 0
    counts = load_baseline(bl)
    (key,) = counts
    core.write_baseline_counts(counts, bl, justifications={key: "because"})
    assert core.load_justifications(bl) == {key: "because"}
    # a rewrite keeps the surviving entry's justification
    capsys.readouterr()
    assert main([str(bad), "--baseline", str(bl), "--write-baseline",
                 "--cache", str(tmp_path / "c.json")]) == 0
    assert core.load_justifications(bl) == {key: "because"}


def test_lint_sources_duplicate_relpath_does_not_crash():
    # lint_sources is the documented multi-file entry point; duplicate
    # relpaths must not crash the graph build's ordering
    pairs = [("mxnet_tpu/x.py", "def f(xs):\n    return [x.item() for x in xs]\n"),
             ("mxnet_tpu/x.py", "def g():\n    return 1\n")]
    found = lint_sources(pairs, passes=["host-sync"])
    assert len(found) == 1


def test_cache_prunes_entries_for_deleted_files(tmp_path):
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("X = 1\n")
    b.write_text("Y = 2\n")
    path = tmp_path / "c.json"
    lint_files([a, b], root=tmp_path, cache=LintCache(path))
    b.unlink()
    lint_files([a], root=tmp_path, cache=LintCache(path))
    import json as _json
    # default extra_sig "" section of the per-baseline-signature layout
    entries = _json.loads(path.read_text())["sections"][""]["files"]
    assert "a.py" in entries and "b.py" not in entries


def test_use_after_donate_intermediate_introspection_not_a_consumer():
    # len()/logging touching a prep'd name first must NOT open the
    # donation window (and must not steal the consumer's identity)
    assert lint("""
        def ok(flat_ws, buckets, fn, log):
            argnums, consumed = donation_prep(flat_ws, buckets)
            n = len(flat_ws)
            log.debug("packing %d", n)
            new_ws = fn(flat_ws, buckets)
            return new_ws
    """, "use-after-donate") == []
    # the real consumer still opens it
    f = lint("""
        def stale(flat_ws, buckets, fn):
            argnums, consumed = donation_prep(flat_ws, buckets)
            n = len(flat_ws)
            new_ws = fn(flat_ws, buckets)
            return flat_ws[0]
    """, "use-after-donate")
    assert len(f) == 1 and "`flat_ws`" in f[0].message


def test_use_after_donate_same_statement_read_after_call():
    # positional order approximates evaluation order: a read AFTER the
    # donating call in one statement is stale...
    f = lint("""
        def bad(opt, idx, g, weights, states):
            out = fused_apply(opt, idx, g, weights, states) + weights[0]
            return out
    """, "use-after-donate")
    assert len(f) == 1 and "`weights`" in f[0].message
    # ...a read BEFORE it is not
    assert lint("""
        def ok(opt, idx, g, weights, states):
            out = weights[0] + fused_apply(opt, idx, g, weights, states)
            return out
    """, "use-after-donate") == []


def test_project_scope_gives_changed_only_cross_file_context(tmp_path):
    # --changed-only semantics: report only changed files, but keep the
    # full scope as graph context so cross-file traced seeds still reach
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    helpers = pkg / "helpers.py"
    steps = pkg / "steps.py"
    helpers.write_text("def helper(x):\n    return x.asnumpy()\n")
    steps.write_text("import jax\n"
                     "from mxnet_tpu.helpers import helper\n\n"
                     "@jax.jit\n"
                     "def step(x):\n"
                     "    return helper(x)\n")
    # changed file alone: no seed visible, false clean
    alone = lint_files([helpers], root=tmp_path,
                       passes=["traced-host-sync"])
    assert alone == []
    # with the unchanged file as graph context: the hazard is visible,
    # and findings still come only from the changed file
    ctxd = lint_files([helpers], root=tmp_path, passes=["traced-host-sync"],
                      project_scope=[helpers, steps])
    assert len(ctxd) == 1 and ctxd[0].path == "mxnet_tpu/helpers.py"


def test_cli_stats_emitted_with_write_baseline(tmp_path, capsys):
    bad = tmp_path / "v.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    assert main([str(bad), "--write-baseline", "--stats",
                 "--baseline", str(tmp_path / "bl.json"),
                 "--cache", str(tmp_path / "c.json")]) == 0
    assert "tpulint --stats:" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# v3: abstract shape/sharding interpreter (tools/tpulint/shapes.py) and the
# recompile-risk / pallas-kernel-check / sharding-flow passes
# ---------------------------------------------------------------------------

from tools.tpulint import shapes  # noqa: E402
from tools.tpulint.shapes import Dim, derived, join_dims  # noqa: E402


def test_dim_lattice_joins():
    c8, c16 = Dim.const(8), Dim.const(16)
    knob = Dim.knob("MXNET_DECODE_SLOTS")
    top = Dim.top("len() of host data")
    unk = Dim.unknown()
    # unknown is the join identity (ignorance is not evidence)
    assert join_dims(unk, c8).kind == "const"
    assert join_dims(c8, unk).value == 8
    # equal consts stay const; distinct sizes join to a bounded set
    assert join_dims(c8, Dim.const(8)).value == 8
    assert join_dims(c8, c16).kind == "bounded"
    assert join_dims(c8, knob).kind == "bounded"
    # top absorbs everything and keeps its origin for the message
    assert join_dims(top, c8).kind == "top"
    assert join_dims(knob, top).origin == "len() of host data"
    # derived arithmetic: top taints, unknown stays unknown
    assert derived(c8, top).kind == "top"
    assert derived(c8, unk).kind == "unknown"
    assert derived(c8, knob).kind == "knob"


def test_recompile_risk_loop_accumulator_into_jit():
    found = lint("""
        import jax
        import numpy as np

        def _impl(x):
            return x * 2

        _STEP = jax.jit(_impl)

        def collate(batches):
            rows = []
            for b in batches:
                rows.append(np.asarray(b))
            return _STEP(np.stack(rows))
    """, "recompile-risk")
    assert len(found) == 1
    assert "⊤" in found[0].message and "_STEP" in found[0].message


def test_recompile_risk_len_of_host_data_into_jit():
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def handle(prompt):
            arr = np.zeros((3, len(prompt)), np.int32)
            return step(arr)
    """, "recompile-risk")
    assert len(found) == 1 and "len()" in found[0].message


def test_recompile_risk_interprocedural_top_flow():
    # the ⊤ array is built in one function, dispatched in another: only
    # the interprocedural parameter summary can see it
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def inner(arr):
            return step(arr)

        def outer(data):
            return inner(np.zeros((len(data), 4)))
    """, "recompile-risk")
    assert len(found) == 1 and "step" in found[0].message


def test_recompile_risk_jit_attr_and_wrapper_dispatch():
    # the decode idiom: jit installed as an instance attribute in
    # __init__, dispatched through telemetry.jit_call on a retry closure
    found = lint("""
        import jax
        import numpy as np

        class Engine:
            def __init__(self, fn):
                self._step = jax.jit(fn)

            def tick(self, host_rows):
                from . import telemetry
                x = np.zeros((len(host_rows),))

                def attempt():
                    return telemetry.jit_call("site", self._step, x)
                return attempt()
    """, "recompile-risk")
    assert len(found) == 1 and "self._step" in found[0].message


def test_recompile_risk_bucket_ladder_and_knob_clean():
    # the sanctioned shapes: select_bucket rungs and get_env knobs are
    # bounded — one compile per rung / per process, warmup covers them
    found = lint("""
        import jax
        import numpy as np
        from .base import get_env
        from .serving.buckets import select_bucket

        @jax.jit
        def step(x):
            return x + 1

        def prefill(prompt, ladder):
            rung = select_bucket(len(prompt), ladder)
            return step(np.zeros((3, rung), np.int32))

        def tick():
            s = get_env("MXNET_DECODE_SLOTS", 8, int, cache=False)
            return step(np.zeros((5, s), np.int32))
    """, "recompile-risk")
    assert found == []


def test_recompile_risk_speculative_widened_step_clean():
    # the ISSUE-20 widened decode tick: the packed operand is
    # (5, slots * (spec_k + 1)) where BOTH factors are get_env knobs.
    # The shape interpreter must resolve the arithmetic over two knob
    # lattice values to `knob` (bounded: one compile per process), not
    # widen to ⊤ and flag the jitted step as a recompile hazard.
    found = lint("""
        import jax
        import numpy as np
        from .base import get_env

        @jax.jit
        def step(x):
            return x + 1

        def spec_tick():
            s = get_env("MXNET_DECODE_SLOTS", 8, int, cache=False)
            k = get_env("MXNET_DECODE_SPEC_K", 0, int, cache=False)
            return step(np.zeros((5, s * (k + 1)), np.int32))
    """, "recompile-risk")
    assert found == []


def test_recompile_risk_warmup_rung_loop_clean():
    # one compile per rung of a knob-parsed ladder is the warmup
    # CONTRACT, not a hazard — bounded by construction
    found = lint("""
        import jax
        import numpy as np
        from .base import get_env

        @jax.jit
        def step(x):
            return x + 1

        def warmup():
            raw = get_env("MXNET_DECODE_PREFILL_BUCKETS", "16,64", str,
                          cache=False)
            ladder = [int(t) for t in str(raw).split(",") if t.strip()]
            for rung in ladder:
                step(np.zeros((3, rung), np.int32))
    """, "recompile-risk")
    assert found == []


def test_recompile_risk_unknown_never_reported():
    # a jit over shapes the interpreter cannot derive must stay silent:
    # the pass reports positively-derived ⊤ only
    found = lint("""
        import jax

        @jax.jit
        def step(x):
            return x + 1

        def run(batch):
            return step(batch)
    """, "recompile-risk")
    assert found == []


def test_recompile_risk_scoped_to_mxnet_tpu():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run(data):
            return step(np.zeros((len(data),)))
    """
    assert lint(src, "recompile-risk", relpath="tools/helper.py") == []
    assert len(lint(src, "recompile-risk")) == 1


def test_pallas_check_off_tile_block_and_sublane():
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((5, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((20, 128), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2
    assert "last dim 100" in msgs and "second-to-last dim 5" in msgs


def test_pallas_check_module_const_folding():
    # LANES/_SUBLANES-style module constants fold into the block check
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        LANES = 128
        HALF = LANES // 2

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(2,),
                in_specs=[pl.BlockSpec((8, HALF), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, LANES), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    assert len(found) == 1 and "last dim 64" in found[0].message


def test_pallas_check_grid_index_map_arity():
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((32, 256), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    assert len(found) == 1 and "arity mismatch" in found[0].message


def test_pallas_check_scalar_prefetch_arity():
    # PrefetchScalarGridSpec appends N scalar refs to every index_map:
    # a lambda that ignores them is an on-device TypeError
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def run(x, tbl, kern):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((8, 128),
                                       lambda i, j, t: (i, j)),
            )
            return pl.pallas_call(
                kern,
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct((32, 256), jnp.float32),
            )(tbl, x)
    """, "pallas-kernel-check")
    assert len(found) == 1
    assert "scalar-prefetch" in found[0].message \
        and "takes 2 argument(s)" in found[0].message


def test_pallas_check_vmem_budget():
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((1024, 2048), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1024, 2048), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((4096, 2048), jnp.float32),
                scratch_shapes=[pltpu.VMEM((1024, 2048), jnp.float32)],
            )(x)
    """, "pallas-kernel-check")
    assert len(found) == 1
    assert "VMEM" in found[0].message and "16 MB" in found[0].message


def test_pallas_check_clean_kernel_negative():
    # tile-aligned blocks, consistent arity, modest VMEM: silent —
    # including symbolic dims the const folder cannot (and must not)
    # guess at
    found = lint("""
        import functools
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        LANES = 128

        def flash(q, k, v, kern, bq, bk, d, n_q, n_kv, b, h, sp):
            return pl.pallas_call(
                kern,
                grid=(b * h, n_q, n_kv),
                in_specs=[
                    pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
                    pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
                ],
                out_specs=pl.BlockSpec((1, bq, d),
                                       lambda bh, qi, ki: (bh, qi, 0)),
                out_shape=jax.ShapeDtypeStruct((8, 128, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((8, LANES), jnp.float32)],
            )(q, k, v)
    """, "pallas-kernel-check")
    assert found == []


def test_pallas_check_scoped_to_mxnet_tpu():
    src = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """
    assert lint(src, "pallas-kernel-check", relpath="example/k.py") == []
    assert len(lint(src, "pallas-kernel-check")) == 1


def test_sharding_flow_undefined_axis():
    found = lint("""
        import numpy as np
        import jax
        from jax import lax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def shard(devs, x):
            mesh = Mesh(np.asarray(devs), ("dp",))
            y = jax.device_put(x, NamedSharding(mesh, P("tp")))
            return lax.psum(y, "model")
    """, "sharding-flow")
    assert len(found) == 2
    msgs = " | ".join(f.message for f in found)
    assert "'tp'" in msgs and "'model'" in msgs


def test_sharding_flow_cross_file_axis_definition():
    # "dp" is defined by a Mesh in another file of the same lint scope:
    # the whole-program axis set must see it
    meshes = """
        import numpy as np
        from jax.sharding import Mesh

        def device_mesh(devs, axis_names=("dp",)):
            return Mesh(np.asarray(devs), tuple(axis_names))
    """
    user = """
        from jax.sharding import NamedSharding, PartitionSpec as P

        def spec(mesh):
            return NamedSharding(mesh, P("dp"))
    """
    found = core.lint_sources(
        [("mxnet_tpu/parallel2.py", textwrap.dedent(meshes)),
         ("mxnet_tpu/user2.py", textwrap.dedent(user))],
        passes=["sharding-flow"])
    assert found == []
    # without the defining file the same use IS a finding
    alone = core.lint_sources([("mxnet_tpu/user2.py", textwrap.dedent(user))],
                              passes=["sharding-flow"])
    assert len(alone) == 1 and "'dp'" in alone[0].message


def test_sharding_flow_bare_p_requires_partitionspec_import():
    # a helper that HAPPENS to be called P must not alias into the check
    found = lint("""
        def P(name):
            return name

        def run():
            return P("whatever")
    """, "sharding-flow")
    assert found == []


def test_sharding_flow_donated_layout_mismatch():
    found = lint("""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            return jax.jit(fn,
                           in_shardings=(P("dp"), P()),
                           out_shardings=(P(), P()),
                           donate_argnums=(0,))
    """, "sharding-flow")
    assert len(found) == 1 and "silent copy" in found[0].message


def test_sharding_flow_donation_clean_cases():
    # matching layouts, and the common out_shardings-only state threading
    found = lint("""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            a = jax.jit(fn,
                        in_shardings=(P("dp"), P()),
                        out_shardings=(P("dp"), P()),
                        donate_argnums=(0,))
            b = jax.jit(fn, out_shardings=(P(), P()),
                        donate_argnums=(0, 1))
            return a, b
    """, "sharding-flow")
    assert found == []


def test_sharding_flow_scoped_to_mxnet_tpu():
    src = """
        from jax.sharding import NamedSharding, PartitionSpec as P

        def spec(mesh):
            return NamedSharding(mesh, P("nowhere"))
    """
    assert lint(src, "sharding-flow", relpath="tools/helper.py") == []
    assert len(lint(src, "sharding-flow")) == 1


# -- seeded shape bugs (fixture): each new pass catches exactly its bug -----

SHAPE_SEEDED = (REPO / "tests" / "fixtures"
                / "tpulint_shape_bugs.py").read_text()
SHAPE_CLEAN = (REPO / "tests" / "fixtures"
               / "tpulint_shape_clean.py").read_text()


def _lint_shape_fixture(src, rule=None):
    return lint_source("mxnet_tpu/_shape_fixture.py", src,
                       passes=[rule] if rule else None)


def test_shape_seeded_bug_recompile_risk():
    f = _lint_shape_fixture(SHAPE_SEEDED, "recompile-risk")
    assert len(f) == 1
    assert "_STEP" in f[0].message and "⊤" in f[0].message


def test_shape_seeded_bug_pallas_kernel_check():
    f = _lint_shape_fixture(SHAPE_SEEDED, "pallas-kernel-check")
    assert len(f) == 1 and "last dim 100" in f[0].message


def test_shape_seeded_bug_sharding_flow():
    f = _lint_shape_fixture(SHAPE_SEEDED, "sharding-flow")
    assert len(f) == 1 and "'tp'" in f[0].message


def test_shape_seeded_bugs_exactly_three_across_all_passes():
    f = _lint_shape_fixture(SHAPE_SEEDED)
    assert sorted(x.rule for x in f) == \
        ["pallas-kernel-check", "recompile-risk", "sharding-flow"]


def test_shape_clean_fixture_zero_findings_all_passes():
    """The false-positive suite: the sanctioned bucket-ladder, warmup,
    knob-shape, scalar-prefetch-pallas and defined-axis idioms produce
    ZERO findings — across the three new passes AND every other pass."""
    assert _lint_shape_fixture(SHAPE_CLEAN) == []


def test_recompile_risk_zero_findings_on_real_serving_plane():
    """Acceptance: the REAL decode engine (bucket ladders, warmed step,
    knob-sized slots) is clean by construction under the abstract
    interpreter — the PR-3 runtime recompile gauge's zero is now a
    statically proven property."""
    serving = [REPO / "mxnet_tpu" / "serving" / p
               for p in ("decode.py", "engine.py", "buckets.py",
                         "batcher.py", "kvcache.py")]
    found = lint_files(serving, passes=["recompile-risk"])
    assert found == [], "\n".join(map(str, found))


# -- cache invalidation on baseline edit (the PR-12 regression) --------------

def test_cache_invalidated_by_baseline_edit(tmp_path, capsys):
    """Editing the baseline must invalidate cached pass results: a warm
    run after dropping a baseline entry re-RUNS the passes and
    re-reports from fresh findings. (Reported findings were already
    correct — cached results are stored pre-baseline — but cache entries
    could outlive the baseline they were computed under; keying the
    cache by baseline content makes the invariant hold at the cache
    layer, and keeps any future baseline-consulting pass correct by
    construction.)"""
    from tools.tpulint.cache import LintCache, baseline_sig

    bad = tmp_path / "v.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    bl = tmp_path / "bl.json"
    cache = tmp_path / "c.json"
    assert main([str(bad), "--baseline", str(bl), "--write-baseline",
                 "--cache", str(cache)]) == 0
    # warm + baselined: clean
    assert main([str(bad), "--baseline", str(bl), "--cache",
                 str(cache)]) == 0
    capsys.readouterr()
    # drop the baseline entry: the SAME warm cache must re-report
    bl.write_text('{"version": 1, "counts": {}}\n')
    assert main([str(bad), "--baseline", str(bl), "--cache",
                 str(cache)]) == 1
    assert "host-sync" in capsys.readouterr().out
    # and the invalidation is at the CACHE layer, not a lucky re-report:
    # a cache opened under the new baseline signature starts cold
    stale = LintCache(cache, extra_sig="different-baseline")
    assert stale.get_local("v.py", "whatever", "host-sync") is None
    assert baseline_sig(bl) != "" and baseline_sig(None) == ""
    assert baseline_sig(tmp_path / "missing.json") == ""


def test_lint_gate_script_syntax_and_exec_bit():
    gate = REPO / "tools" / "lint_gate.sh"
    assert gate.exists()
    import os
    assert os.access(str(gate), os.X_OK), "tools/lint_gate.sh must be +x"
    check = subprocess.run(["bash", "-n", str(gate)], capture_output=True,
                           text=True)
    assert check.returncode == 0, check.stderr


# -- review hardening: pinned fixes -----------------------------------------

def test_sharding_flow_axis_name_kwarg_does_not_self_define():
    # an `axis_name=` kwarg on a COLLECTIVE is a use, not a definition —
    # it must not legitimize its own typo'd axis
    found = lint("""
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh

        def collect(devs, x):
            mesh = Mesh(np.asarray(devs), ("dp",))
            return lax.psum(x, axis_name="bogus")
    """, "sharding-flow")
    assert len(found) == 1 and "'bogus'" in found[0].message
    # ...while the same kwarg on a mesh CONSTRUCTOR does define the axis
    clean = lint("""
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh

        def sequence_mesh(devices, axis_name="sp"):
            return Mesh(np.asarray(devices), (axis_name,))

        def run(devs, x):
            mesh = sequence_mesh(devs, axis_name="sp")
            return lax.psum(x, axis_name="sp")
    """, "sharding-flow")
    assert clean == []


def test_pallas_check_smem_scratch_exempt():
    # SMEM is scalar memory: no (sublane, lane) tiling, not in the VMEM
    # pool — the standard (1, 1) scalar scratch must not be flagged or
    # counted into the budget
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
                scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32)],
            )(x)
    """, "pallas-kernel-check")
    assert found == []


def test_write_baseline_rekeys_cache_to_new_baseline(tmp_path):
    # --write-baseline changes the baseline content: the cache must be
    # re-keyed to the NEW baseline so the next run starts warm (not a
    # silently cold "warm" lap that trips the lint_gate time gate)
    from tools.tpulint.cache import LintCache, baseline_sig

    bad = tmp_path / "v.py"
    bad.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    bl = tmp_path / "bl.json"
    cache = tmp_path / "c.json"
    assert main([str(bad), "--baseline", str(bl), "--write-baseline",
                 "--cache", str(cache)]) == 0
    warm = LintCache(cache, extra_sig=baseline_sig(bl))
    # entries survived the re-key: a hit under the NEW baseline signature
    (rel,) = [k for k in warm._entries if k.endswith("v.py")]
    assert warm.get_local(rel, warm._entries[rel]["sha"],
                          "host-sync") is not None


def test_lint_gate_broken_environment_exits_2(tmp_path):
    # a crashing linter (rc >= 2) must exit the GATE with 2 — not be
    # misread as "new findings" via an empty JSON file
    fake = tmp_path / "fakepy"
    fake.write_text("#!/bin/sh\nexit 3\n")
    fake.chmod(0o755)
    proc = subprocess.run([str(REPO / "tools" / "lint_gate.sh")],
                          env={"PATH": "/usr/bin:/bin",
                               "PYTHON": str(fake)},
                          capture_output=True, text=True)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "failed (rc=3)" in proc.stderr


def test_recompile_risk_loop_counter_widens_to_top():
    # a loop-carried scalar counter over unbounded data is a ⊤ dim —
    # folding it once would claim a positively-WRONG constant shape
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run(batches):
            n = 0
            for b in batches:
                n += 1
            return step(np.zeros((n,)))
    """, "recompile-risk")
    assert len(found) == 1 and "python-loop counter" in found[0].message
    # ...but a counter over a BOUNDED iterable inherits the bound
    clean = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run():
            n = 0
            for b in (16, 64, 256):
                n += 1
            return step(np.zeros((n,)))
    """, "recompile-risk")
    assert clean == []


def test_pallas_check_vmem_budget_uses_kernel_dtype():
    # a bf16 kernel's blocks are bf16: ~8 MB true footprint must NOT be
    # counted at f32 width into a fake over-ceiling finding
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((1024, 2048), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
            )(x)
    """, "pallas-kernel-check")
    assert found == []


def test_recompile_risk_bounded_loop_append_is_clean():
    # fixed-shape accumulate over a literal tuple: the accumulator's
    # length is the (bounded) trip count, not ⊤
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run():
            rows = []
            for r in (16, 64):
                rows.append(np.zeros((8, 128)))
            return step(np.stack(rows))
    """, "recompile-risk")
    assert found == []


def test_recompile_risk_keyword_operand_flagged():
    # a ⊤-shaped operand passed BY KEYWORD traces exactly like a
    # positional one
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x=None):
            return x + 1

        def run(data):
            return step(x=np.zeros((len(data),)))
    """, "recompile-risk")
    assert len(found) == 1 and "`x`" in found[0].message


def test_pallas_check_defaulted_index_map_params_ok():
    # lambda i, j=0: legally callable with 1 arg — not an arity mismatch
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i, j=0: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    assert found == []


def test_sharding_flow_posonly_defaults_alignment():
    # positional-only params with defaults must not shift the
    # axis_names default out of (or a non-axis string into) the
    # definition set
    found = lint("""
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh

        def make(devices="cpu", /, axis_names=("dp",)):
            return Mesh(np.asarray(devices), tuple(axis_names))

        def run(x):
            return lax.psum(x, "dp")
    """, "sharding-flow")
    assert found == []
    bogus = lint("""
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh

        def make(devices="cpu", /, axis_names=("dp",)):
            return Mesh(np.asarray(devices), tuple(axis_names))

        def run(x):
            return lax.psum(x, "cpu")
    """, "sharding-flow")
    assert len(bogus) == 1 and "'cpu'" in bogus[0].message


def test_recompile_risk_min_clamp_is_bounded():
    # min(len(data), CAP) takes finitely many values: the cap idiom is
    # warmup-precompilable, not a storm
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run(data):
            n = min(len(data), 128)
            return step(np.zeros((n,)))
    """, "recompile-risk")
    assert found == []
    # ...but max() over ⊤ is genuinely unbounded
    storm = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run(data):
            n = max(len(data), 128)
            return step(np.zeros((n,)))
    """, "recompile-risk")
    assert len(storm) == 1


def test_pallas_check_vmem_budget_multi_output_dtype():
    # out_shape as a LIST of ShapeDtypeStructs (multi-output kernel)
    # must still feed the bf16 element size into the budget
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((1024, 2048), lambda i: (i, 0))],
                out_specs=[pl.BlockSpec((16, 128), lambda i: (i, 0)),
                           pl.BlockSpec((16, 128), lambda i: (i, 0))],
                out_shape=[jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
                           jax.ShapeDtypeStruct((64, 128), jnp.bfloat16)],
            )(x)
    """, "pallas-kernel-check")
    assert found == []


def test_pallas_check_bf16_sublane_applies_to_in_specs():
    # the kernel dtype (from out_shape) governs EVERY block: an (8, 128)
    # input block in a bf16 kernel is off the (16, 128) min tile
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
            )(x)
    """, "pallas-kernel-check")
    assert len(found) == 1
    assert "second-to-last dim 8" in found[0].message \
        and "bfloat16" in found[0].message


def test_pallas_check_reassigned_local_not_folded():
    # a name assigned twice has no trustworthy value: the (8, 128)
    # runtime block must not be flagged with the STALE first value
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            bs = 100
            bs = 128
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, bs), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    assert found == []


def test_recompile_risk_nested_comprehension_binds_own_iter():
    # the inner generator's target binds from ITS iterator: y is a
    # bounded ladder rung, not the outer ⊤ loop index
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run(data, ladder=(16, 64)):
            return [step(np.zeros((y, 4)))
                    for x in range(len(data)) for y in ladder]
    """, "recompile-risk")
    assert found == []
    # inverse: a ⊤ INNER iterator behind a bounded first generator
    storm = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def run(data, ladder=(16, 64)):
            return [step(np.zeros((n, 4)))
                    for b in ladder for n in range(len(data))]
    """, "recompile-risk")
    assert len(storm) == 1


def test_lint_gate_unparseable_output_exits_2(tmp_path):
    # a linter that exits 0 but emits garbage stdout is a broken tool
    # (rc 2), not "new findings" (rc 1)
    fake = tmp_path / "fakepy"
    fake.write_text("#!/bin/sh\n"
                    "case \"$1\" in\n"
                    "  -m) echo 'not json'; exit 0 ;;\n"
                    # the heredoc check runs under the same $PY: delegate
                    # to the real python so json parsing actually runs
                    "  *) exec python3 \"$@\" ;;\n"
                    "esac\n")
    fake.chmod(0o755)
    proc = subprocess.run([str(REPO / "tools" / "lint_gate.sh")],
                          env={"PATH": "/usr/bin:/bin",
                               "PYTHON": str(fake)},
                          capture_output=True, text=True)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "unparseable" in proc.stderr


def test_pallas_check_dtype_keyword_argument():
    # ShapeDtypeStruct((...), dtype=jnp.bfloat16): the keyword spelling
    # must feed the tile tables exactly like the positional one
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128),
                                               dtype=jnp.bfloat16),
            )(x)
    """, "pallas-kernel-check")
    assert len(found) == 1 and "bfloat16" in found[0].message


def test_sharding_flow_donation_resolves_named_specs():
    # an out_shardings referenced through a variable must compare equal
    # to the literal it was assigned from — no manufactured mismatch
    found = lint("""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            out_spec = P("dp")
            return jax.jit(fn,
                           in_shardings=(P("dp"),),
                           out_shardings=(out_spec,),
                           donate_argnums=(0,))
    """, "sharding-flow")
    assert found == []


def test_recompile_risk_posonly_nested_param_shadows_closure():
    # a positional-only param of a nested def shadows the ⊤ closure
    # variable: callers decide its shape, the closure value is stale
    found = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def outer(items):
            acc = []
            for i in items:
                acc.append(np.asarray(i))
            batch = np.stack(acc)

            def attempt(batch, /):
                return step(batch)
            return attempt
    """, "recompile-risk")
    assert found == []


def test_join_values_elts_monotone_across_call_sites():
    # two sites passing the same literal shape keep the tuple; a ⊤
    # element survives a join against a const one (summary can't mask a
    # storm-passing site)
    from tools.tpulint.shapes import AbsValue, Dim, join_values

    t1 = AbsValue(elts=(AbsValue(dim=Dim.const(8)),
                        AbsValue(dim=Dim.const(16))))
    t2 = AbsValue(elts=(AbsValue(dim=Dim.const(8)),
                        AbsValue(dim=Dim.const(16))))
    same = join_values(t1, t2)
    assert same.elts is not None and same.elts[1].dim.value == 16
    t3 = AbsValue(elts=(AbsValue(dim=Dim.const(8)),
                        AbsValue(dim=Dim.top("len() of host data"))))
    mixed = join_values(join_values(t1, t3), t2)
    assert mixed.elts is not None and mixed.elts[1].dim.kind == "top"


def test_sharding_flow_donation_name_bound_tuple():
    # out_shardings referenced as a Name-bound TUPLE must expand to its
    # elements, not compare as one opaque spec
    found = lint("""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            specs = (P("dp"),)
            return jax.jit(fn,
                           in_shardings=(P("dp"),),
                           out_shardings=specs,
                           donate_argnums=(0,))
    """, "sharding-flow")
    assert found == []


def test_pallas_check_positional_out_shape_dtype():
    # out_shape passed POSITIONALLY (pallas_call's 2nd parameter) must
    # feed the dtype tables like the keyword spelling
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern,
                jax.ShapeDtypeStruct((64, 128), jnp.bfloat16),
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
            )(x)
    """, "pallas-kernel-check")
    assert len(found) == 1 and "bfloat16" in found[0].message


def test_cache_sections_alternating_modes_both_warm(tmp_path):
    # a --no-baseline run between gate runs must not evict the default
    # section: each baseline signature owns its own entries
    a = tmp_path / "a.py"
    a.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    path = tmp_path / "c.json"
    lint_files([a], root=tmp_path, cache=LintCache(path, extra_sig="bl1"))
    lint_files([a], root=tmp_path, cache=LintCache(path, extra_sig=""))
    warm1 = LintCache(path, extra_sig="bl1")
    lint_files([a], root=tmp_path, cache=warm1)
    assert warm1.misses == 0 and warm1.hits > 0
    warm2 = LintCache(path, extra_sig="")
    lint_files([a], root=tmp_path, cache=warm2)
    assert warm2.misses == 0 and warm2.hits > 0


def test_lint_gate_works_through_symlink(tmp_path):
    # the documented pre-commit wiring is a SYMLINK into .git/hooks —
    # the gate must resolve it before deriving the repo root
    link = tmp_path / "pre-commit"
    link.symlink_to(REPO / "tools" / "lint_gate.sh")
    proc = subprocess.run([str(link)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint_gate: clean" in proc.stdout


def test_sharding_flow_donation_conditional_reassignment_bails():
    # a spec reassigned across branches has no single provable value:
    # picking either branch would report a mismatch no execution path
    # contains — the check must bail
    found = lint("""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        def build(mesh, fn, devs, compat):
            m = Mesh(devs, ("dp", "mp"))
            in_spec = P("mp")
            out_spec = P("dp")
            if compat:
                in_spec = P("dp")
            else:
                out_spec = P("mp")
            return jax.jit(fn,
                           in_shardings=(in_spec,),
                           out_shardings=(out_spec,),
                           donate_argnums=(0,))
    """, "sharding-flow")
    assert found == []


def test_sharding_flow_donation_spelling_variants_compare_equal():
    # P("dp") vs PartitionSpec("dp") vs NamedSharding(mesh, P("dp")) are
    # the SAME layout — spelling must not manufacture a mismatch
    found = lint("""
        import jax
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                                  PartitionSpec as P)

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            return jax.jit(fn,
                           in_shardings=(P("dp"), NamedSharding(m, P())),
                           out_shardings=(PartitionSpec("dp"), P()),
                           donate_argnums=(0, 1))
    """, "sharding-flow")
    assert found == []


def test_cache_sections_capped_lru(tmp_path):
    # superseded baseline signatures are pruned LRU on save — the file
    # cannot grow one orphaned full-scope section per baseline edit
    from tools.tpulint.cache import MAX_SECTIONS

    a = tmp_path / "a.py"
    a.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    path = tmp_path / "c.json"
    for i in range(MAX_SECTIONS + 3):
        lint_files([a], root=tmp_path,
                   cache=LintCache(path, extra_sig="bl%d" % i))
    data = json.loads(path.read_text())
    assert len(data["sections"]) <= MAX_SECTIONS
    assert "bl%d" % (MAX_SECTIONS + 2) in data["sections"]  # newest kept


def test_recompile_risk_chained_knob_parse_clean():
    # the chained spelling `get_env(..., typ=str).split(",")` carries
    # the same knob-str provenance as the assigned-name spelling
    found = lint("""
        import jax
        import numpy as np
        from .base import get_env

        @jax.jit
        def step(x):
            return x + 1

        def warmup():
            rungs = [int(s) for s in
                     get_env("MXNET_BUCKETS", "1,4", typ=str).split(",")]
            for r in rungs:
                out = []
                for _ in range(4):
                    out.append(np.zeros((r, 8)))
                step(np.stack(out))
    """, "recompile-risk")
    assert found == []


def test_cache_warm_runs_persist_lru_stamp(tmp_path):
    # fully-warm laps must persist their recency, or eviction retires
    # the most-actively-used section while keeping dead ones
    from tools.tpulint.cache import MAX_SECTIONS

    a = tmp_path / "a.py"
    a.write_text("def f(xs):\n    return [x.asnumpy() for x in xs]\n")
    path = tmp_path / "c.json"
    lint_files([a], root=tmp_path, cache=LintCache(path, extra_sig="hot"))
    for sig in ("cold1", "cold2"):
        lint_files([a], root=tmp_path, cache=LintCache(path, extra_sig=sig))
    # warm re-use of "hot" (no pass runs) must still refresh its stamp
    warm = LintCache(path, extra_sig="hot")
    lint_files([a], root=tmp_path, cache=warm)
    assert warm.misses == 0
    # push past the cap with fresh signatures: "hot" survives, the
    # stalest cold section is evicted
    for i in range(MAX_SECTIONS - 1):
        lint_files([a], root=tmp_path,
                   cache=LintCache(path, extra_sig="new%d" % i))
    data = json.loads(path.read_text())
    assert "hot" in data["sections"]
    assert "cold1" not in data["sections"]


def test_sharding_flow_donation_trailing_none_padding():
    # P("dp") == P("dp", None): PartitionSpec pads trailing dims
    found = lint("""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            return jax.jit(fn,
                           in_shardings=(P("dp", None),),
                           out_shardings=(P("dp"),),
                           donate_argnums=(0,))
    """, "sharding-flow")
    assert found == []


def test_sharding_flow_donation_bails_on_static_argnums():
    # static args shift donate_argnums vs in_shardings: unprovable
    found = lint("""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        def build(mesh, fn, devs):
            m = Mesh(devs, ("dp",))
            return jax.jit(fn, static_argnums=(0,),
                           in_shardings=(P("dp"), P(None)),
                           out_shardings=(P("dp"),),
                           donate_argnums=(1,))
    """, "sharding-flow")
    assert found == []


def test_pallas_check_unfoldable_local_shadows_module_const():
    # a runtime-chosen local TILE shadows the module-level TILE = 100:
    # the stale module value must not manufacture a tile finding
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        TILE = 100

        def run(x, kern, pick_tile):
            TILE = pick_tile(x)
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, TILE), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    assert found == []


def test_pallas_check_positional_prefetch_grid_spec():
    # PrefetchScalarGridSpec(3, grid=(4, 2), ...) — positional
    # num_scalar_prefetch must feed the arity check
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def run(x, tbl, kern):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                1,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128),
                                       lambda i, j, t: (t[i], j))],
                out_specs=pl.BlockSpec((8, 128),
                                       lambda i, j, t: (i, j)),
            )
            return pl.pallas_call(
                kern, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct((32, 256), jnp.float32),
            )(tbl, x)
    """, "pallas-kernel-check")
    assert found == []


def test_pallas_check_loop_target_shadows_module_const():
    # a for-loop target shadowing a module const must drop the name
    # from the folder — no finding about a value no path holds
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        W = 100

        def run(x, kern):
            outs = []
            for W in (128, 256):
                outs.append(pl.pallas_call(
                    kern, grid=(4,),
                    in_specs=[pl.BlockSpec((8, W), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                )(x))
            return outs
    """, "pallas-kernel-check")
    assert found == []


def test_pallas_check_posonly_lambda_params_counted():
    # lambda i, /, j: two positional params — not an arity mismatch
    # against a 2-dim grid
    found = lint("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def run(x, kern):
            return pl.pallas_call(
                kern, grid=(4, 4),
                in_specs=[pl.BlockSpec((8, 128),
                                       index_map=lambda i, /, j: (i, j))],
                out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((32, 512), jnp.float32),
            )(x)
    """, "pallas-kernel-check")
    assert found == []


# ---------------------------------------------------------------------------
# unattributed-dispatch (the ISSUE-18 perf-attribution gate)
# ---------------------------------------------------------------------------

def test_unattributed_dispatch_pass_registered():
    assert "unattributed-dispatch" in core.all_passes()


def test_unattributed_dispatch_flags_direct_and_resilience_not_wrapped():
    src = """
        import jax
        from mxnet_tpu import resilience, telemetry

        _STEP = jax.jit(lambda x: x * 2)

        def attributed(x):
            return telemetry.jit_call("plane.step", _STEP, x)

        def bare(x):
            return _STEP(x)

        def retried(x):
            # retries the dispatch but attributes nothing
            return resilience.call("plane.step", _STEP, x)
    """
    found = lint(src, "unattributed-dispatch")
    assert len(found) == 2
    msgs = "\n".join(f.message for f in found)
    assert "telemetry.jit_call" in msgs  # the fix is named in the message
    assert "resilience.call" in msgs
    # outside mxnet_tpu/ the pass does not apply
    assert lint(src, "unattributed-dispatch", relpath="tools/x.py") == []


def test_unattributed_dispatch_decorated_call_by_name():
    found = lint("""
        import jax

        @jax.jit
        def _kernel(x):
            return x + 1

        def run(x):
            return _kernel(x)
    """, "unattributed-dispatch")
    assert len(found) == 1
    assert "@jit-decorated" in found[0].message


def test_unattributed_dispatch_wrapped_sites_are_clean():
    assert lint("""
        import jax
        from mxnet_tpu import telemetry

        _STEP = jax.jit(lambda x: x * 2)

        def a(x):
            return telemetry.jit_call("plane.a", _STEP, x)

        def b(x):
            return telemetry.jit_call("plane.b", _STEP, x, donate=True)
    """, "unattributed-dispatch") == []


def test_unattributed_dispatch_repo_gate_clean_and_justified():
    # the serving/train planes dispatch ONLY through telemetry.jit_call;
    # the sanctioned bypasses (warmup laps, fused-optimizer internals,
    # kernel-module plumbing under already-wrapped engine sites) ride
    # the baseline WITH a justification each
    files = collect_files(["mxnet_tpu"], root=REPO)
    findings = [f for f in lint_files(files, root=REPO,
                                      passes=["unattributed-dispatch"])]
    baseline = load_baseline(DEFAULT_BASELINE)
    assert apply_baseline(findings, baseline) == []
    justs = core.load_justifications(DEFAULT_BASELINE)
    for f in findings:
        assert justs.get(f.baseline_key()), \
            "unattributed-dispatch baseline entries must carry a " \
            "justification: %s" % f.baseline_key()
    # the decode engine's steady-state loop itself is fully attributed:
    # its only baselined survivor is the warmup lap
    decode = [f for f in findings if "serving/decode" in f.path]
    assert all("warmup" in (justs.get(f.baseline_key()) or "").lower()
               or "warm" in (justs.get(f.baseline_key()) or "").lower()
               for f in decode)
