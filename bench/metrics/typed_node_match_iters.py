"""Bid iterations of the fused program's node match per window round on a
cluster of several GPU types (the program's ``fused_node_iters``), rounds
that fell back to the host planner included, where the program keeps their
counters: the reader of ``node_match_iters``."""

from bench.metrics.node_match_iters import read  # noqa: F401
