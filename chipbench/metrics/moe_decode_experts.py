"""Experts routed to per MoE layer in one decode step, the mean over
every decode step of the process's ``generate`` calls: the program's
routing counter (``_routing``). It sets how many expert weights a step
streams, the bytes of ``moe_decode_roofline``. None without the
counter."""
from chipbench.metrics._routing import mean_experts


def read(run):
    return mean_experts("decode")
