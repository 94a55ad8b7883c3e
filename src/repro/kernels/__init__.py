"""Pallas TPU kernels for the compute hot spots, with jnp oracles in ref.py.

flash_attention   tiled online-softmax attention, GQA-native (train/prefill)
decode_attention  KV-cache streaming single-token attention (decode shapes)
rglru_scan        RG-LRU linear recurrence (recurrentgemma, long_500k)
mlstm_scan        chunkwise-parallel mLSTM matrix memory (xlstm)
slstm_scan        sequential sLSTM with VMEM-resident state (xlstm)
"""

from . import ref  # noqa: F401
