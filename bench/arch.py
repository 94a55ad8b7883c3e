"""The program's model built from a configuration's file.

The configuration states the model's layout (blocks, where the sLSTM
blocks sit, widths); the program selects a model by the name of a
registered ``ArchConfig``. :func:`register` builds that ``ArchConfig``
from the file's numbers and registers it in the calling process, so each
process that builds the model calls it first."""

from __future__ import annotations


def register(c: dict) -> str:
    """Register the configuration's xLSTM in this process under
    ``bench.<config name>``; that name."""
    from repro.configs import base
    from repro.models.xlstm import XLSTMDims

    d, nh = c["d_model"], c["num_heads"]
    dims = XLSTMDims(d_model=d, n_heads=nh,
                     conv_width=c["conv1d_kernel_size"],
                     proj_factor=c["mlstm_proj_factor"],
                     ff_factor=c["slstm_ff_factor"])
    if dims.d_inner != c["mlstm_inner_dim"] \
            or int(d * dims.ff_factor) != c["slstm_ff_dim"]:
        raise ValueError(f"{c['name']}: the widths disagree with the "
                         "projection factors")
    pattern = tuple("slstm" if i in c["slstm_at"] else "mlstm"
                    for i in range(c["num_blocks"]))
    cfg = base.ArchConfig(
        name=f"bench.{c['name']}", family="ssm", n_layers=c["num_blocks"], d_model=d,
        n_heads=nh, n_kv_heads=nh, d_ff=0, vocab_size=c["vocab_size"],
        pattern=pattern, scan_layers=False, xlstm=dims, norm=c["norm"],
        norm_eps=c["norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        subquadratic=True, source=c["source"])
    base.register(cfg, cfg)
    return cfg.name

