"""The longest span ``worker.claim_chip`` over the cell's workers, in
seconds: a worker opening its TPU chip before its hello
(``bench/spans.py``)."""

from bench import spans


def read(obs: dict):
    claims = spans.startup("worker.claim_chip")
    return max(spans.duration(s) for s in claims) / 1e9 if claims else None
