"""The simulator's import boundary: the modules that users run load nothing of
``repro`` outside the simulator, its configurations, the service and the
compile cache."""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

ENTRY_MODULES = ("repro.core.dse", "repro.core.suite", "repro.core.search",
                 "repro.core.surrogate", "repro.core.telemetry",
                 "repro.serve.sim_service", "repro.configs.vector_engine")

ALLOWED = {"repro", "repro.compile_cache", "repro.core", "repro.configs",
           "repro.configs.vector_engine", "repro.serve",
           "repro.serve.sim_service"}


def test_simulator_entry_points_load_only_the_simulator():
    # a fresh interpreter, so modules other tests imported do not count
    code = ("import importlib, json, sys\n"
            f"for m in {ENTRY_MODULES!r}:\n"
            "    importlib.import_module(m)\n"
            "print(json.dumps(sorted(m for m in sys.modules\n"
            "                        if m == 'repro' or m.startswith('repro.'))))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (SRC, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    loaded = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(ENTRY_MODULES) <= set(loaded)
    stray = [m for m in loaded
             if m not in ALLOWED and not m.startswith("repro.core.")]
    assert stray == [], stray
