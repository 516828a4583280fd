"""Copy the matrix that an `Explorer` serves into the reference's plain
form: per cell its raw dependency graphs and runs, as numpy copies.

This is the one place where the benchmark reads the program's model of
the hardware: the graphs its builder derived from the modelled
accelerators and the traced programs.  Nothing of the evaluator under test
(condensation, packing, compiled functions, baselines) is read.
"""

from __future__ import annotations

from typing import List

import numpy as np

from reference import Cell, Graph


def _graph(aidg) -> Graph:
    names = [None] * len(aidg.classes)
    for name, cid in aidg.classes.items():
        names[cid] = name
    return Graph(
        fu=np.array(aidg.fu_lat, np.float64),
        mem=np.array(aidg.mem_lat, np.float64),
        base=np.array(aidg.base, np.float64),
        preds=np.array(aidg.preds, np.int64),
        extra=np.array(aidg.pred_extra, np.float64),
        op_class=np.array(aidg.op_class, np.int64),
        class_names=names,
        op_scale=np.array(aidg.op_scale, np.float64),
        mem_words=np.array(aidg.mem_words, np.float64),
        storages=[(name, np.array(nodes, np.int64),
                   np.array(aidg.storage_lat[name], np.float64),
                   int(aidg.storage_slots[name]))
                  for name, nodes in aidg.storage_nodes.items()])


def plain_cells(explorer) -> List[Cell]:
    """The explorer's cells in matrix-column order.  Tile graphs that the
    program shares between cells are shared here too, so the reference
    evaluates each once per call."""
    seen = {}

    def graph(aidg):
        g = seen.get(id(aidg))
        if g is None:
            g = seen[id(aidg)] = _graph(aidg)
        return g

    cells = []
    for cs in explorer.compiled:
        stack = getattr(cs, "stack", None)
        if stack is None:
            graphs = [graph(cs.aidg)]
            runs = [(0, 1.0)]
        else:
            if cs.scenario.mode != "sequential":
                raise ValueError(f"{cs.name}: the reference composes "
                                 f"sequential networks only")
            graphs = [graph(p.aidg) for p in stack.problems]
            runs = [(int(li), float(r)) for li, r in
                    zip(stack.run_layer, stack.run_reps)]
        cells.append(Cell(cs.name, cs.arch, cs.workload, graphs, runs))
    return cells
