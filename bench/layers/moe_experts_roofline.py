"""The held-expert matmuls' share of their roofline, in percent: the least
time the chip could take for them (the larger of their operations over
the bfloat16 peak and their bytes over the HBM bandwidth,
``bench/peaks.json``) over the device time of the operations under the
program's ``moe.experts`` scope in the traced window
(``bench/scopes.py``). Operations and bytes are counted from the routed
pairs (``bench/flops_dsv2.py``: ``expert_work``) of each traced request
(counter ``moe.routed``, ``bench/counters.py``), averaged over them and
taken for each ``jit_eval_loss`` program in the window."""

from bench import counters
from bench.flops import peak
from bench.flops_dsv2 import expert_work


def read(obs: dict):
    tr = obs.get("trace") or {}
    secs = tr.get("scopes", {}).get("moe.experts")
    n = tr.get("programs", {}).get("jit_eval_loss", {}).get("n")
    routed = counters.values("moe.routed")
    if not secs or not n or not routed:
        return None
    pk = peak(obs["device_kind"])
    roof = 0.0
    for r in routed:
        ops, nbytes = expert_work(obs["config"], r)
        roof += max(ops / pk["bf16_flops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * roof / len(routed) * n / secs
