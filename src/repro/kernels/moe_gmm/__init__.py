from .ops import held_expert_ffn, held_expert_gmm
from .ref import held_expert_gmm_ref

__all__ = ["held_expert_ffn", "held_expert_gmm", "held_expert_gmm_ref"]
