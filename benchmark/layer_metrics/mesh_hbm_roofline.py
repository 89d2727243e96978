"""Share of the HBM roofline of a query sharded over chips: the least
bytes one chip must read (the reference's ``min_bytes``, a chip's
share) over one chip's peak, over the devices' mean busy time of the
trace (``hbm_roofline`` divides a whole query's bytes by one chip's
peak, which reads four times high on four chips)."""

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
