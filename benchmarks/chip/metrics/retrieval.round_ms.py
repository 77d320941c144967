"""Mean host-clock time of one ``TeleRAGEngine.retrieve`` call in the
window (device and host partitions and the merge; the call returns
numpy, so it has waited for the device)."""


def read(w):
    if not w.retrievals:
        return None
    return 1e3 * sum(r.t1 - r.t0 for r in w.retrievals) / len(w.retrievals)
