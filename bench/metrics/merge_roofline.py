"""The three token-merge kernels' share of their roofline together, in %:
the least time their calls need (the model family's ``kernel_costs``) over
their device time in the trace."""
from bench.results import roofline


def read(run):
    return roofline(run, ("knn_density", "merge_assign", "unmerge_scatter"))
