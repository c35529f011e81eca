"""A plan's folded kernel nodes as the tests count them, apart from the
executor.

``folded_nodes`` is what ``runtime.PlanPrograms.segments_folded`` should
count for a plan: the kernel nodes, in scheduled order, with a partial
tile range all of whose ops an earlier node of the same tenant covers.
"""

from repro.core.runtime import plan_kernels, plan_tenants


def folded_nodes(plan):
    """The kernel nodes whose ops earlier kernel nodes already cover."""
    sns = [{s.name: s for s in tg.supernodes} for tg in plan_tenants(plan)]
    covered = [set() for _ in sns]
    out = []
    for t, name in plan_kernels(plan):
        sn = sns[t][name]
        if not sn.full and covered[t].issuperset(sn.op_names):
            out.append((t, name))
        covered[t].update(sn.op_names)
    return out
