# L6 API whole fit: `fit_kernel`'s self time per fit in ms: its duration less
# the union of its own children's (the fit report's tree, `span_parents`):
# what runs inside the kernel under no span of its own.  The mean over the
# window's fits.
from chipbench import span_reads


def read(ctx):
    per_fit = [span_reads.self_seconds(f, "fit_kernel") for f in ctx["fits"]]
    per_fit = [s for s in per_fit if s is not None]
    return 1e3 * sum(per_fit) / len(per_fit) if per_fit else None
