"""The plain reference of a sweep: every grid point and replica through the
float64 heap engine (``pipesim_ref.core.des``, a frozen copy of the repro
package's numpy engine and spec compilers; see ``pipesim_ref/__init__.py``).

It follows the sweep semantics the program documents: points in the
Cartesian order of the axes, replica ``r`` of a point compiling its
scenario, fleet and reliability draws with seed ``spec.seed + 1000 * r``,
and one summary per replica, averaged over replicas where a point has more
than one. It takes nothing the program made: the workloads come from the
benchmark's generator, everything else from the copies here.
"""
from __future__ import annotations

from pipesim_ref.core import des, trace
from pipesim_ref.core.engines import (_aggregate_replicas, _spec_workloads,
                                      _summarize)


def run_point(spec) -> dict:
    """``{"traces": [SimTrace per replica], "records": TaskRecords,
    "summary": dict, "replica_summaries": [dict] or None}``."""
    wls, compiled, fleets, probe, rels = _spec_workloads(spec, None)
    traces, recs, sums = [], [], []
    for r, w in enumerate(wls):
        comp = compiled[r] if compiled is not None else None
        rel = rels[r] if rels is not None else None
        tr = des.simulate(w, spec.platform, spec.policy, scenario=comp,
                          fleet=fleets[r] if fleets is not None else None,
                          probe=probe, reliability=rel)
        rec = trace.flatten_trace(tr, w)
        traces.append(tr)
        recs.append(rec)
        sums.append(_summarize(spec, rec, comp, tr, rel=rel))
    if spec.n_replicas == 1:
        return dict(traces=traces, records=recs[0], summary=sums[0],
                    replica_summaries=None)
    agg = _aggregate_replicas(spec, sums, recs, 0.0)
    return dict(traces=traces, records=agg.records, summary=agg.summary,
                replica_summaries=sums)


def run(sweep) -> list:
    """One :func:`run_point` result per grid point, in the sweep's order."""
    return [run_point(spec) for spec in sweep.points()]
