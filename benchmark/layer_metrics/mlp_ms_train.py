"""Layer: client trainer (``models/mpt.py``). Device milliseconds of a step
under the scope ``block/mlp``: the non-expert MLP's products (``up_proj`` /
``down_proj``, ``gate_proj`` too where it is a SwiGLU; an expert model's
leading dense block), the activation between them and the residual add,
forward, backward and what ``remat`` runs again. The expert layer is not
here (``moe_*_ms_train``). A fusion carries its root's name: a weight
gradient stacked by the layer scan counts here as long as XLA names the
fusion after the product (PERF.md section 5 says where it did). One part of
``benchmark/trace/step_parts.py``'s partition. Moves ``train_tokens_per_s``."""

from benchmark.trace.step_parts import part_ms_per_step


def read(run, reduction):
    return part_ms_per_step(run, reduction, "mlp")
