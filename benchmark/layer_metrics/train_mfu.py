"""Trainer: model FLOPs utilization, end to end: the operations forward and
backward need per token (from shapes; recomputation not counted) times tokens
per second over chips times the chip's bf16 peak."""
from benchmark import roofline


def read(facts, trace):
    rate = facts.get("train_tok_per_s")
    if not rate or not facts.get("peaks"):
        return None
    flops = roofline.train_flops_per_token(facts["dims"], facts["seq"])
    return 100.0 * flops * rate / (facts["chips"]
                                   * facts["peaks"]["bf16_flops"])
