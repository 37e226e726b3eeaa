"""Whole-graph BFS from a source: ``GraphSession.run(BFS(root))`` or a
served ``BFS``.

Compared: every answer's hop distances against the plain level-
synchronous BFS, vertex by vertex (``bfs_mismatched_vertices``, exact,
limit 0). Each compared answer also gets the Graph500 edge count that
``teps`` reads. Control (distances state no precision): the reference
stopped one level short, which breaks the guarantee that every
reachable vertex gets its distance.
"""
from __future__ import annotations

import numpy as np

import reference


def make(spec, config):
    from repro.algorithms import BFS

    return BFS


def check(plain, kept, spec, config, inputs):
    bad = 0
    for a, root, got in kept:
        ref = reference.bfs(plain, root)
        bad += int(np.count_nonzero(np.asarray(got, np.int64) != ref))
        a.info["edges"] = reference.traversed_tuples(inputs.t_src, ref)
    return {"bfs_mismatched_vertices": (bad, 0)}


def control(plain, sources, spec, config):
    bad = 0
    for s in sources:
        full = reference.bfs(plain, s)
        depth = int(full[full < reference.UNREACHED].max())
        bad += int(np.count_nonzero(
            reference.bfs(plain, s, max_levels=depth - 1) != full))
    return {"bfs_mismatched_vertices": (bad, 0)}
