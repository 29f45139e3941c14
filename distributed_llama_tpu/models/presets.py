"""Published model geometries the repo runs at full width.

BASELINE.json config counterparts that fit (or are layer-scaled to fit) one 16 GB
chip. MoE geometries keep the real per-layer shape, the honest per-layer decode
cost, with n_layers cut to fit HBM; the name records the cut. Each value is the
keyword set of a ModelSpec. Read by bench.py (--arch) and by
examples/make_tiny_model.py (--arch), which chip_smoke.py drives.
"""

from __future__ import annotations

from .spec import ArchType, HiddenAct, RopeType

ARCHS = {
    "llama2_7b": dict(arch_type=ArchType.LLAMA, dim=4096, hidden_dim=11008,
                      n_layers=32, n_heads=32, n_kv_heads=32, vocab_size=32000,
                      seq_len=2048, rope_type=RopeType.LLAMA),
    "tinyllama_1_1b": dict(arch_type=ArchType.LLAMA, dim=2048, hidden_dim=5632,
                           n_layers=22, n_heads=32, n_kv_heads=4, vocab_size=32000,
                           seq_len=2048, rope_type=RopeType.LLAMA),
    "llama3_8b": dict(arch_type=ArchType.LLAMA, dim=4096, hidden_dim=14336,
                      n_layers=32, n_heads=32, n_kv_heads=8, vocab_size=128256,
                      seq_len=2048, rope_theta=500000.0, rope_type=RopeType.LLAMA),
    "mixtral_8x7b_l8": dict(arch_type=ArchType.MIXTRAL, dim=4096, hidden_dim=14336,
                            n_layers=8, n_heads=32, n_kv_heads=8, vocab_size=32000,
                            seq_len=2048, n_experts=8, n_active_experts=2,
                            rope_type=RopeType.FALCON),
    "grok1_l2": dict(arch_type=ArchType.GROK1, dim=6144, hidden_dim=32768,
                     n_layers=2, n_heads=48, n_kv_heads=8, vocab_size=131072,
                     seq_len=2048, n_experts=8, n_active_experts=2,
                     hidden_act=HiddenAct.GELU, rope_type=RopeType.FALCON),
}
