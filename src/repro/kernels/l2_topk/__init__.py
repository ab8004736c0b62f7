from .ops import l2_topk, L2TopKConfig, NodeOperands, prepare_node
from .ref import l2_topk_ref

__all__ = ["l2_topk", "L2TopKConfig", "NodeOperands", "prepare_node",
           "l2_topk_ref"]
