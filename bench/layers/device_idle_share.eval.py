"""Share of the traced window in which no XLA operation ran on the chip,
in percent: 100 * (1 - busy / window), busy being the union of the
device's operation intervals (``bench/trace.py``)."""


def read(obs: dict):
    tr = obs.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]
