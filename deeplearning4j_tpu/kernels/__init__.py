"""Pallas TPU kernels — the hand-written-kernel tier.

Ref role: libnd4j's hand-tuned CPU/CUDA kernels (N2/N4). On TPU, XLA
fusion covers almost everything (SURVEY.md §2.1 mapping note); Pallas is
reserved for ops where explicit VMEM scheduling beats the fusion
autoscheduler — attention being the canonical case (per
/opt/skills/guides/pallas_guide.md).
"""
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .paged_attention import paged_attention, paged_prefill_attention
from .selective_scan import selective_scan_chunk, selective_scan_step

# (`moe_experts.expert_ffn` is imported by the one layer that runs it:
# its import pulls in megablox, which no other model should pay for)
__all__ = ["flash_attention", "decode_attention", "paged_attention",
           "paged_prefill_attention", "selective_scan_chunk",
           "selective_scan_step"]
