"""Share of the rows the held-expert matmuls computed that carry no
routed (token, expert) pair, in percent, over the traced requests:
100 * (1 - pairs / rows), from the program's counters ``moe.routed``
(pairs routed to each held expert of each MoE layer) and ``moe.rows``
(rows each layer's expert matmuls computed; ``bench/counters.py``)."""

from bench import counters


def read(obs: dict):
    routed, rows = counters.values("moe.routed"), counters.values("moe.rows")
    total = sum(sum(r) for r in rows)
    if not routed or not total:
        return None
    pairs = sum(sum(sum(layer) for layer in r) for r in routed)
    return 100.0 * (1.0 - pairs / total)
