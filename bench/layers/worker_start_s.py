"""Seconds from ``plan("cluster")`` to every worker's hello with its chip
claimed: the blocking start of the backend, on the driver's clock."""


def read(obs: dict):
    return obs.get("worker_start_s")
