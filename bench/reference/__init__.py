"""Plain reference of the certified-bracket path, in numpy and scipy.

Independent of ``repro``: it imports nothing of the program. It takes from
a run only the graph that the benchmark generated and the answers under
check. The decomposition is random (the program draws its centers with
``jax.random``), so no reference can redo it; it is checked by its
certificate instead (``est_ClusterQuotientEstimator``), and everything that
follows from it is computed anew.
"""
