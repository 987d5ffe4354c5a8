"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships as a triple:
    <name>.py — ``pl.pallas_call`` with explicit BlockSpec VMEM tiling
    ops.py    — jit'd public wrappers with shape plumbing; they compile the
                kernels on a TPU and interpret them on any other backend
    ref.py    — pure-jnp oracles the tests assert against

Kernels:
    seg_sum         — segment sums (flat_profile, stragglers busy-sum)
    pair_sum        — weighted 2-D scatter-add (comm_matrix, load_imbalance)
    hist_bin        — histogram counts (message_histogram)
    time_bin        — Pipit's time_profile overlap histogram (the paper's
                      hottest analysis loop, §IV-B) as an events×bins tiler
    flash_attention — blocked causal/local GQA attention (MXU 128-aligned)
    topk_gating     — MoE router top-k gating with fused softmax
"""
