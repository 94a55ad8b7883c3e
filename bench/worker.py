"""State and reports of a worker process that holds a chip.

Task bodies are shipped with a snapshot of their globals, so a body that
keeps something between tasks keeps it here, in a module the worker
imports: ``from bench import worker; worker.STATE``."""

from __future__ import annotations

#: what a configuration's bodies keep between tasks on this worker
STATE: dict = {}


def device_memory() -> dict:
    """This worker's device report and its device memory peak."""
    import jax
    from repro.core.backends.cluster_worker import device_report
    report = device_report()
    report["peak_bytes"] = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    return report
