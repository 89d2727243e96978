"""Share of the HBM roofline: the bytes the traced operations must
move (from the configuration's shapes, by the reference's
``min_bytes``; never from the implementation) over the device's peak
bandwidth, over the device-busy time of the trace."""

from lib.peaks import peak


def read(run):
    if not run.trace or run.rehearsal or not run.trace["busy_s"]:
        return None
    cell = run.cell
    ops = sum(1 for r in run.traced_records if r["ok"])
    least = ops * cell.reference.min_bytes(
        cell.sizes, cell.traffic["params"]) / peak(
        run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / run.trace["busy_s"]
