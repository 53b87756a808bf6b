#!/usr/bin/env python
"""API-freeze + op-desc compat tooling — parity with the reference's
tools/diff_api.py (API.spec gate: public signatures may not drift silently)
and tools/check_op_desc.py (op registry compatibility: ops/grads may not
vanish or change differentiability between releases).

Usage:
  python tools/api_spec.py generate   # rewrite tools/API.spec + OP_DESC.spec
  python tools/api_spec.py check      # exit 1 on drift (what the test runs)
"""
import inspect
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

API_SPEC = os.path.join(REPO, "tools", "API.spec")
OP_SPEC = os.path.join(REPO, "tools", "OP_DESC.spec")

_MODULES = [
    "paddle_tpu",
    "paddle_tpu.layers",
    "paddle_tpu.layers.nn",
    "paddle_tpu.layers.tensor",
    "paddle_tpu.layers.sequence",
    "paddle_tpu.layers.detection",
    "paddle_tpu.layers.control_flow",
    "paddle_tpu.layers.rnn",
    "paddle_tpu.optimizer",
    "paddle_tpu.io",
    "paddle_tpu.metrics",
    "paddle_tpu.regularizer",
    "paddle_tpu.dygraph",
    "paddle_tpu.contrib.slim.prune",
    # paddle-2.0-preview namespaces
    "paddle_tpu.tensor",
    "paddle_tpu.nn",
    "paddle_tpu.nn.functional",
    "paddle_tpu.nn.functional.conv",
    "paddle_tpu.nn.functional.loss",
    "paddle_tpu.nn.initializer",
    "paddle_tpu.metric",
    "paddle_tpu.imperative",
    "paddle_tpu.declarative",
    "paddle_tpu.framework",
]


def collect_api():
    import importlib

    lines = []
    for modname in _MODULES:
        mod = importlib.import_module(modname)
        names = getattr(mod, "__all__", None) or [
            n for n in dir(mod) if not n.startswith("_")]
        for n in sorted(set(names)):
            obj = getattr(mod, n, None)
            if obj is None or inspect.ismodule(obj):
                continue
            try:
                if inspect.isclass(obj):
                    sig = str(inspect.signature(obj.__init__))
                    kind = "class"
                elif callable(obj):
                    sig = str(inspect.signature(obj))
                    kind = "def"
                else:
                    continue
            except (ValueError, TypeError):
                continue
            lines.append(f"{modname}.{n} ({kind}) {sig}")
    return sorted(set(lines))


def collect_op_desc():
    import paddle_tpu  # noqa: F401 — registers every op
    from paddle_tpu.framework import registry
    from paddle_tpu.framework.executor import _HOST_OPS

    out = {}
    for name in registry.all_op_types():
        spec = registry.get_op_spec(name)
        grad = ("custom" if callable(spec.grad)
                else "none" if spec.grad is None else "auto")
        out[name] = {
            "grad": grad,
            "diff_inputs": list(spec.diff_inputs or []) or None,
            "needs_rng": bool(spec.needs_rng),
            "is_optimizer": bool(spec.is_optimizer),
            # inference-coverage column (static analysis, ISSUE 6):
            # "declared" = a registered infer_shape spec fills output
            # metadata directly; "eval_shape" = build-time inference leans
            # on abstract-evaluating the lowering (registry.py fallback).
            # The analysis shape checker's `no_inference` findings name
            # ops where the fallback cannot abstract the lowering — fill
            # those with registry.set_infer_shape / register_op(
            # infer_shape=...) and this column flips to "declared".
            "infer": ("declared" if spec.infer_shape is not None
                      else "eval_shape"),
        }
    for name in sorted(_HOST_OPS):
        out.setdefault(name, {"host": True})
    return out


def generate():
    with open(API_SPEC, "w") as f:
        f.write("\n".join(collect_api()) + "\n")
    with open(OP_SPEC, "w") as f:
        json.dump(collect_op_desc(), f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {API_SPEC} and {OP_SPEC}")


def check():
    """Returns a list of human-readable violations (empty = clean)."""
    problems = []
    want_api = set(open(API_SPEC).read().splitlines())
    have_api = set(collect_api())
    for line in sorted(want_api - have_api):
        problems.append(f"API removed/changed: {line}")
    # additions are allowed (growing the surface is fine); removals are not

    want_ops = json.load(open(OP_SPEC))
    have_ops = collect_op_desc()
    for name, spec in want_ops.items():
        if name not in have_ops:
            problems.append(f"op removed: {name}")
            continue
        got = have_ops[name]
        if spec.get("host") != got.get("host"):
            problems.append(f"op {name}: host/device flip")
            continue
        if spec.get("host"):
            continue
        if spec["grad"] != got["grad"]:
            problems.append(
                f"op {name}: grad mode {spec['grad']} -> {got['grad']}")
        if spec["grad"] != "none" and spec.get("diff_inputs") and \
                not set(spec["diff_inputs"]) <= set(got.get("diff_inputs")
                                                    or spec["diff_inputs"]):
            problems.append(f"op {name}: diff_inputs shrank")
    return problems


def main():
    cmd = sys.argv[1] if len(sys.argv) > 1 else "check"
    if cmd == "generate":
        generate()
        return
    problems = check()
    for p in problems:
        print(p)
    print(f"{len(problems)} problems")
    sys.exit(1 if problems else 0)


if __name__ == "__main__":
    main()
