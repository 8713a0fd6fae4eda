"""mamba2-2.7b [ssm] — attention-free SSD stack (no FFN).
[arXiv:2405.21060; hf:state-spaces/mamba2-2.7b]

Sizes from https://huggingface.co/state-spaces/mamba2-2.7b (config.json):
d_model 2560, n_layer 64, d_intermediate 0, vocab_size 50277 padded to a
multiple of 16 (50288 rows), rms_norm, residual_in_fp32, tie_embeddings.
The layer keeps the defaults of ``Mamba2`` (mamba_ssm/modules/mamba2.py):
d_state 128, d_conv 4 with a conv bias, expand 2, headdim 64 (80 heads),
ngroups 1, no projection bias, and a gated RMSNorm after the gate; every
RMSNorm has eps 1e-5.
"""
from repro.models.config import ArchConfig, LayerPattern


def config() -> ArchConfig:
    return ArchConfig(
        name="mamba2-2.7b", family="ssm",
        n_layers=64, d_model=2560, n_heads=0, n_kv_heads=0, head_dim=0,
        d_ff=0, vocab_size=50288,
        norm_kind="rmsnorm", rms_norm_eps=1e-5,
        tie_embeddings=True, residual_in_fp32=True,
        pattern=(LayerPattern("ssm", "none"),),
        ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_groups=1,
        ssm_conv_width=4,
    )


def reduced() -> ArchConfig:
    return config().reduced()
