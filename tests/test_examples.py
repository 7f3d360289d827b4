"""Examples stay loadable AND runnable.

Fast lane: each script under examples/ must import cleanly (API drift in
the public surface breaks them at import time). Slow lane
(DL4J_TPU_SLOW=1 / `pytest -m slow`): every example's main() executes
headlessly at toy sizes in a subprocess — the reference's
examples-as-tests culture (MultiLayerTest.java et al. are runnable
mini-examples).
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_EX = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "examples")
SCRIPTS = sorted(f for f in os.listdir(_EX) if f.endswith(".py"))

# toy-size kwargs for mains that take sizes; {} = defaults already toy.
# char_rnn keeps its default steps: its main asserts sample quality, and
# post-compile steps are cheap — compile time dominates either way.
_TINY_ARGS = {
    "lenet_mnist.py": {"epochs": 1, "batch": 64, "train_examples": 256,
                       "test_examples": 128},
}


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_imports(script):
    spec = importlib.util.spec_from_file_location(
        f"example_{script[:-3]}", os.path.join(_EX, script))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)   # runs top-level code, not main()
        assert hasattr(mod, "main"), f"{script} has no main()"
    finally:
        sys.modules.pop(spec.name, None)


@pytest.mark.slow
@pytest.mark.parametrize("script", SCRIPTS)
def test_example_main_runs(script):
    """Execute the example end to end (subprocess: clean JAX state, no
    cross-example jit-cache or platform leakage)."""
    kwargs = _TINY_ARGS.get(script, {})
    runner = (
        "import json, runpy, sys;"
        "ns = runpy.run_path(sys.argv[1]);"
        "ns['main'](**json.loads(sys.argv[2]))"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(_EX)]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    # virtual devices SPLIT the host's XLA threadpool: an 8-device pool
    # makes single-device examples ~8x slower. Only the mesh example gets 8.
    n_dev = 8 if script == "data_parallel_training.py" else 1
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_dev}"])
    r = subprocess.run(
        [sys.executable, "-c", runner, os.path.join(_EX, script),
         json.dumps(kwargs)],
        capture_output=True, text=True, timeout=900, cwd=os.path.dirname(_EX),
        env=env)
    assert r.returncode == 0, (
        f"{script} main({kwargs}) failed:\n{r.stdout[-2000:]}\n"
        f"{r.stderr[-3000:]}")
