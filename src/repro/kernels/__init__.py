"""Pallas TPU kernels for the serving/training compute hot-spots:
flash attention (prefill) and the MoE grouped matmul.

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec),
ops.py (jit wrapper: Mosaic on TPU, interpret mode on CPU only), and
ref.py (pure-jnp oracle used by the allclose test sweeps).

The paper itself contributes no kernels (its mechanism is an ISA /
scheduler change realized in the simulator); these kernels are the
framework's compute layer, and their 128-aligned tile shapes define
the μTOp granularity used by the NeuISA compiler (DESIGN.md §3).
"""
