"""The stub family's weights: the Qwen2 family's (`program.QWEN2`)."""

from .program import QWEN2

make, shapes = QWEN2.weights.make, QWEN2.weights.shapes
