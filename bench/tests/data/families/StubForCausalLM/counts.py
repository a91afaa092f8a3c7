"""The stub family's counts: the Qwen2 family's (`program.QWEN2`)."""

from .program import QWEN2

param_count, weight_bytes = QWEN2.counts.param_count, QWEN2.counts.weight_bytes
kv_bytes_per_token = QWEN2.counts.kv_bytes_per_token
window_work = QWEN2.counts.window_work
