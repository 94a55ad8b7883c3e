"""The jitted per-request loss's share of the chip's peak, in percent:
forward operations per request, counted from the configuration's shapes
(``bench/flops.py``), times the requests traced, over the device time of
the ``jit_eval_loss`` programs in the trace times the peak of the device
kind (``bench/peaks.json``)."""

from bench.flops import peak


def read(obs: dict):
    tr = obs.get("trace")
    prog = (tr or {}).get("programs", {}).get("jit_eval_loss")
    if not prog or not prog["n"] or prog["s"] <= 0:
        return None
    work = obs["eval_flops"] * prog["n"]
    return 100.0 * work / (prog["s"]
                           * peak(obs["device_kind"])["bf16_flops_per_s"])
