"""Each benchmark test starts with this process's spans and counters let
go: a traced run at test size files them in the driver's ring, and the
per-layer readers read whatever the ring holds."""

import pytest


@pytest.fixture(autouse=True)
def empty_trace_ring():
    from repro.core import tracing
    tracing.reset()
