"""Paper-scale example model (134M params): the kind of dynamic NLP model
ORLOJ serves (GPT/BART class, Table 1).  ``chip_smoke.py`` and
``python -m repro.launch.serve`` serve it at this full width on one TPU
chip.  The engine-substrate eval tier (``repro.eval.substrate`` registers
it as ``orloj_gpt``) serves ``CONFIG.reduced()`` toy sizes, so that its
engine cells also run on a host CPU."""
from ..models.config import ModelConfig

# Bucket/batch grid the serving examples and the paper-size engine profile
# serve this model with (one compiled program per (bucket, batch) shape).
SERVE_BUCKETS = (32, 64, 128, 256)
SERVE_BATCH_SIZES = (1, 2, 4, 8)

CONFIG = ModelConfig(
    name="orloj-gpt",
    arch_type="dense",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    d_ff=3072,
    vocab_size=32000,
    norm="layernorm",
    mlp="gelu",
    source="paper Table 1 (GPT-class)",
)
