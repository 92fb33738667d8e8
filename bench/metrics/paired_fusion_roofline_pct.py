"""Share of its roofline that the fusion step reaches: the Pallas
``paired_fusion`` kernel (``kernels/paired_fusion.py``) over every
parameter leaf of the cohort. The least time that can take is the larger
of its bytes over the HBM bandwidth and its FLOPs over the bf16 peak,
both counted from the leaf sizes (``bench/work/<family>.py``).

The time is, per round, from the end of the local phase's loop to the
end of the last kernel call in the round program: the kernel calls, the
padding around them, and the copies in which XLA prefetches their
operands into on-chip memory. The kernel ops alone leave those copies
out, and then read above the HBM roofline on a cohort of 100."""
from bench.trace import phase_ns

KERNEL = ("paired_fusion",)


def read(ctx):
    ns = max(phase_ns(d, "round_fn", "while", KERNEL)
             for d in ctx.summary["devices"].values())
    if not ns:
        return None
    leaves = ctx.work.leaf_sizes(ctx.model)
    nbytes = sum(ctx.work.paired_fusion_bytes(m, ctx.cohort) for m in leaves)
    flops = sum(ctx.work.paired_fusion_flops(m, ctx.cohort) for m in leaves)
    least_s = max(nbytes / ctx.peak["hbm_bytes_per_s"],
                  flops / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least_s * ctx.rounds / (ns / 1e9)
