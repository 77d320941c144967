"""``probe_and_topk``'s share of its roofline (%): the least time its
calls in the traced window require over the kernel's summed device time.

The count is the work a call requires (``counts.probe_topk_call``):
every query scores every centroid, then the vectors on the resident
pages of its probed clusters (the round's ``hit_clusters``).  It is not
the pages the kernel happens to walk, so it reads the same whatever
implements the search."""

import sys

from benchmarks.chip import counts, xplane

NAMES = ("probe_topk_fused",)


def read(w):
    if w.trace is None or w.peak is None:
        return None
    ns, calls = xplane.kernel_ns(w.device_ops(), w.trace_window, NAMES)
    if not calls:
        return None
    flops = nbytes = 0.0
    for r in w.traced_retrievals:
        per_q = [int(sum(w.cluster_pages[c] for c in h))
                 for h in r.hit_clusters]
        distinct = int(sum(w.cluster_pages[c]
                           for c in set().union(*map(set, r.hit_clusters))))
        f, b = counts.probe_topk_call(
            len(r.queries), len(w.cluster_pages), r.queries.shape[1], per_q,
            distinct, w.page_size)
        flops += f
        nbytes += b
    share, bound = counts.roofline(flops, nbytes, ns * 1e-9, w.peak)
    print(f"# probe_and_topk: {calls} calls in the trace, "
          f"{len(w.traced_retrievals)} retrieve calls; {flops:.6g} FLOPs, "
          f"{nbytes:.6g} bytes, {ns * 1e-9:.6f} s; {bound} bound",
          file=sys.stderr)
    return share
