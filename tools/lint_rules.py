#!/usr/bin/env python
"""Repo-specific AST lint rules — the mx.check `static` CI stage.

Each rule encodes a bug class this repo actually shipped (or a
convention another mx.check layer depends on), checked at the SOURCE
level so it fails the PR, not the pod:

  * `shard-map-import` — `jax.shard_map` / `jax.experimental.shard_map`
    imported or referenced anywhere but `parallel/_compat.py`. The
    spelling has moved between jax releases and that breakage shipped
    twice (PR 5 and PR 6, three dist tests each). Everything imports it
    from `_compat`, so the next move is a one-line fix.
  * `signal-handler-blocking` — a blocking call (`.wait()`, `.join()`,
    `.acquire()`, `time.sleep`, `os.waitpid`, `select`) inside a
    function installed with `signal.signal(...)`. PR 5's launch.py
    deadlocked exactly this way: the handler's `Popen.wait()` blocked
    on the `_waitpid_lock` the interrupted main thread already held.
    Handlers set a flag; the main loop does the work.
  * `raw-lock` — `threading.Lock()` / `threading.RLock()` constructed
    directly in an instrumented module instead of through
    `_locklint.make_lock/make_rlock`. Raw locks are invisible to the
    tsan-lite acquisition-order analysis, so a raw lock in an analyzed
    module silently punches a hole in the deadlock detector.
  * `wallclock-in-jit` — `time.time()` / `time.perf_counter()` /
    `datetime.now()` inside a function passed to `jax.jit`. The call
    runs ONCE at trace time and bakes a stale constant into the
    executable — the classic "why is my timestamp frozen" tracing bug.
  * `pallas-call-outside-lib` — `pl.pallas_call` invoked anywhere but
    `mxnet_tpu/pallas_ops/`. Every kernel must live in the mx.kernels
    library: that is where the `kernels=off|auto|on` knob, the
    bit-exact XLA fallback, the interpret-mode CPU test path, and the
    bench_kernels coverage are enforced — a stray pallas_call
    elsewhere has none of them (and silently breaks the kernels=off
    no-pallas-import fast path ci sanity asserts).

Suppress a finding inline with a `# mx.check: disable=<rule>` comment on
the offending line. Stdlib-only; exits 1 when any finding survives.

Usage:
  python tools/lint_rules.py                 # lint the default tree
  python tools/lint_rules.py path [path...]  # lint specific files/dirs
  python tools/lint_rules.py --list-rules
"""
from __future__ import annotations

import argparse
import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the only module allowed to touch jax's shard_map spelling
SHARD_MAP_HOME = os.path.join("mxnet_tpu", "parallel", "_compat.py")

#: modules whose locks must ride the tsan-lite analysis (adopted in this
#: tree; tools/launch.py loads _locklint by path to stay jax-free)
INSTRUMENTED = (
    os.path.join("mxnet_tpu", "telemetry.py"),
    os.path.join("mxnet_tpu", "diagnostics.py"),
    os.path.join("mxnet_tpu", "dataflow.py"),
    os.path.join("mxnet_tpu", "resilience.py"),
    os.path.join("mxnet_tpu", "inspect.py"),
    os.path.join("mxnet_tpu", "memsafe.py"),
    os.path.join("mxnet_tpu", "profiler.py"),
    os.path.join("mxnet_tpu", "config.py"),
    os.path.join("mxnet_tpu", "check.py"),
    os.path.join("mxnet_tpu", "trace.py"),
    os.path.join("mxnet_tpu", "serve.py"),
    os.path.join("mxnet_tpu", "scope.py"),
    os.path.join("tools", "launch.py"),
)

#: call names considered blocking inside a signal handler. `get` and
#: `recv` are deliberately absent: dict.get / os.environ.get /
#: config.get share the bare name with queue.Queue.get and would drown
#: the rule in false positives — those blocking variants are the dynamic
#: lock analysis's job, not this static pass's
BLOCKING_NAMES = ("wait", "join", "acquire", "waitpid", "sleep", "select")

RULES = {
    "shard-map-import": "direct jax shard_map import/reference outside "
                        "parallel/_compat.py (bit PR 5 and PR 6)",
    "signal-handler-blocking": "blocking call inside a signal handler "
                               "(PR 5's launch.py deadlock)",
    "raw-lock": "raw threading.Lock()/RLock() in an instrumented module "
                "(invisible to the tsan-lite lock-order analysis)",
    "wallclock-in-jit": "wall-clock call inside a jitted function (runs "
                        "once at trace time, bakes a stale constant)",
    "pallas-call-outside-lib": "direct pl.pallas_call outside "
                               "mxnet_tpu/pallas_ops/ (kernels belong in "
                               "the mx.kernels library: knob, fallback, "
                               "interpret tests, bench coverage)",
}

#: the only package allowed to invoke pl.pallas_call
PALLAS_HOME = os.path.join("mxnet_tpu", "pallas_ops") + os.sep


class Finding:
    def __init__(self, rule, path, line, message):
        self.rule = rule
        self.path = path
        self.line = line
        self.message = message

    def __str__(self):
        rel = os.path.relpath(self.path, REPO)
        return f"{rel}:{self.line}: [{self.rule}] {self.message}"


def _suppressed_lines(source):
    """{lineno: set(rules)} from `# mx.check: disable=rule[,rule]`
    comments ('all' suppresses every rule on that line)."""
    out = {}
    for i, line in enumerate(source.splitlines(), 1):
        marker = "# mx.check: disable="
        if marker in line:
            rules = line.split(marker, 1)[1].split("#")[0].strip()
            out[i] = {r.strip() for r in rules.split(",") if r.strip()}
    return out


def _dotted(node):
    """Dotted name of an Attribute/Name chain ('' when dynamic)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def rule_shard_map_import(path, tree, source):
    if path.endswith(SHARD_MAP_HOME):
        return []
    out = []
    remed = ("import it from mxnet_tpu.parallel._compat (the one module "
             "owning the jax spelling)")
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in ("jax", "jax.experimental") and any(
                    a.name == "shard_map" for a in node.names):
                out.append(Finding(
                    "shard-map-import", path, node.lineno,
                    f"direct `from {mod} import shard_map` — the "
                    "spelling moves between jax versions; " + remed))
            elif mod.startswith("jax") and "shard_map" in mod:
                out.append(Finding(
                    "shard-map-import", path, node.lineno,
                    f"direct import from `{mod}` — " + remed))
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax") and "shard_map" in a.name:
                    out.append(Finding(
                        "shard-map-import", path, node.lineno,
                        f"direct `import {a.name}` — " + remed))
        elif isinstance(node, ast.Attribute):
            dotted = _dotted(node)
            if dotted in ("jax.shard_map", "jax.experimental.shard_map",
                          "jax.experimental.shard_map.shard_map"):
                out.append(Finding(
                    "shard-map-import", path, node.lineno,
                    f"direct `{dotted}` reference — " + remed))
    return out


def _handler_names(tree):
    """Names of functions installed as signal handlers in this module:
    `signal.signal(SIG, fn)` / `signal.signal(SIG, self.fn)` — plus
    anything named like a handler wired through a dict/partial is out of
    static reach and stays the dynamic lock analysis's job."""
    handlers = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _dotted(node.func) not in ("signal.signal", "_signal.signal"):
            continue
        if len(node.args) >= 2:
            target = node.args[1]
            name = None
            if isinstance(target, ast.Name):
                name = target.id
            elif isinstance(target, ast.Attribute):
                name = target.attr
            if name:
                handlers.add(name)
    return handlers


def rule_signal_handler_blocking(path, tree, source):
    handlers = _handler_names(tree)
    if not handlers:
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or node.name not in handlers:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            fn = call.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else "")
            dotted = _dotted(fn)
            if name in BLOCKING_NAMES or dotted in (
                    "time.sleep", "os.waitpid", "select.select"):
                out.append(Finding(
                    "signal-handler-blocking", path, call.lineno,
                    f"`{dotted or name}(...)` inside signal handler "
                    f"'{node.name}': a handler interrupts a thread that "
                    "may hold the very lock this blocks on (PR 5's "
                    "launch.py deadlocked in Popen.wait). Set a flag; "
                    "let the main loop block."))
        # `with lock:` inside a handler is an acquire too
        for w in ast.walk(node):
            if isinstance(w, (ast.With, ast.AsyncWith)):
                for item in w.items:
                    d = _dotted(item.context_expr)
                    if d and "lock" in d.lower():
                        out.append(Finding(
                            "signal-handler-blocking", path, w.lineno,
                            f"`with {d}:` inside signal handler "
                            f"'{node.name}' blocks on a lock the "
                            "interrupted thread may hold. Set a flag; "
                            "let the main loop lock."))
    return out


def rule_raw_lock(path, tree, source):
    if not any(path.endswith(m) for m in INSTRUMENTED):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted in ("threading.Lock", "threading.RLock"):
            kind = dotted.rsplit(".", 1)[1]
            out.append(Finding(
                "raw-lock", path, node.lineno,
                f"raw `{dotted}()` in an instrumented module: invisible "
                "to the tsan-lite lock-order analysis. Use "
                f"`_locklint.make_{'rlock' if kind == 'RLock' else 'lock'}"
                "('module.purpose')` (plain primitive when disarmed, "
                "order-recording under MXNET_TPU_CHECK_THREADS=1)."))
    return out


def _jitted_function_names(tree):
    """Names of local functions passed to jax.jit(...) in this module
    (the first positional argument), plus functions decorated @jax.jit."""
    jitted = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in (
                "jax.jit", "jit") and node.args:
            a = node.args[0]
            if isinstance(a, ast.Name):
                jitted.add(a.id)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                d = _dotted(dec if not isinstance(dec, ast.Call)
                            else dec.func)
                if d in ("jax.jit", "jit"):
                    jitted.add(node.name)
    return jitted


def rule_wallclock_in_jit(path, tree, source):
    jitted = _jitted_function_names(tree)
    if not jitted:
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or node.name not in jitted:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            dotted = _dotted(call.func)
            if dotted in ("time.time", "time.perf_counter",
                          "time.monotonic", "time.process_time",
                          "datetime.now", "datetime.datetime.now",
                          "datetime.utcnow",
                          "datetime.datetime.utcnow"):
                out.append(Finding(
                    "wallclock-in-jit", path, call.lineno,
                    f"`{dotted}()` inside jitted function "
                    f"'{node.name}': runs ONCE at trace time and bakes "
                    "that instant into the executable as a constant. "
                    "Pass the timestamp in as an argument, or measure "
                    "outside the jit."))
    return out


def rule_pallas_call_outside_lib(path, tree, source):
    rel = os.path.relpath(path, REPO)
    if rel.startswith(PALLAS_HOME):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted and dotted.split(".")[-1] == "pallas_call":
            out.append(Finding(
                "pallas-call-outside-lib", path, node.lineno,
                f"`{dotted}(...)` outside mxnet_tpu/pallas_ops/: kernels "
                "live in the mx.kernels library, behind the kernels knob "
                "with an XLA fallback and an interpret-mode test — add "
                "the kernel there and call its public entry point."))
    return out


ALL_RULES = (rule_shard_map_import, rule_signal_handler_blocking,
             rule_raw_lock, rule_wallclock_in_jit,
             rule_pallas_call_outside_lib)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

#: directories never linted (generated/vendored/fixture trees)
SKIP_DIRS = {".git", "__pycache__", "node_modules", ".pytest_cache",
             "build", "dist"}

#: default lint roots: framework + tools + examples + benchmarks (tests
#: carry deliberate hazard fixtures and suppress inline where needed)
DEFAULT_ROOTS = ("mxnet_tpu", "tools", "examples", "benchmarks",
                 "bench.py", "tests")


def lint_source(path, source, rules=ALL_RULES):
    """Findings for one file's source (the unit tests drive this)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding("parse-error", path, e.lineno or 0, str(e))]
    suppressed = _suppressed_lines(source)
    out = []
    for rule in rules:
        for f in rule(path, tree, source):
            sup = suppressed.get(f.line, ())
            if f.rule in sup or "all" in sup:
                continue
            out.append(f)
    return out


def lint_file(path, rules=ALL_RULES):
    with open(path, encoding="utf-8") as fh:
        return lint_source(path, fh.read(), rules)


def iter_py(roots):
    for root in roots:
        root = os.path.join(REPO, root) if not os.path.isabs(root) else root
        if os.path.isfile(root):
            if root.endswith(".py"):
                yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="mx.check repo-specific AST rules (the CI static "
        "stage); exits 1 on any unsuppressed finding")
    ap.add_argument("paths", nargs="*", default=[],
                    help="files/directories to lint (default: the repo's "
                    "framework + tools + examples + tests trees)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, doc in RULES.items():
            print(f"{name:26s} {doc}")
        return 0

    roots = args.paths or list(DEFAULT_ROOTS)
    findings = []
    n_files = 0
    for path in iter_py(roots):
        n_files += 1
        findings.extend(lint_file(path))
    for f in findings:
        print(f)
    if findings:
        print(f"lint_rules: {len(findings)} finding(s) in {n_files} files",
              file=sys.stderr)
        return 1
    print(f"lint_rules: clean ({n_files} files, "
          f"{len(RULES)} rules)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
