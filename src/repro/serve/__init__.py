"""Simulation-as-a-service over the vector-engine timing model
(`sim_service`).

The submodule is imported lazily so ``python -m repro.serve.sim_service``
doesn't double-import the module it is executing.
"""
_EXPORTS = {
    "Arrival": "sim_service", "ServeReport": "sim_service",
    "SimRequest": "sim_service", "SimResult": "sim_service",
    "SimService": "sim_service", "poisson_arrivals": "sim_service",
    "run_workload": "sim_service",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.serve' has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(f"repro.serve.{mod}"), name)
