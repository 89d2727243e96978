"""Share of the chip-to-chip interconnect's peak that the web join's
hash exchange reaches: the least bytes one chip sends the others in a
query (the reference's ``exchange_bytes``, from the configuration's
sizes: three quarters of its web rows under uniform hashing) over the
peak, over ``exchange_ms`` (pack and unpack included, so the share is
of the whole exchange, not of the all-to-all alone)."""

from lib.scopes import scope_ms

# one TPU v5e chip's interconnect, 1,600 Gbit/s (Google Cloud
# documentation, "TPU v5e"); benchmark/peaks/ holds no interconnect
ICI_BYTES_PER_S = 1600e9 / 8


def read(run):
    cell = run.cell
    ms = scope_ms(run, cell.traffic.get("exchange_scopes", []))
    if not ms or not hasattr(cell.reference, "exchange_bytes"):
        return None
    least = cell.reference.exchange_bytes(cell.sizes, cell.traffic["params"])
    return 100.0 * least / ICI_BYTES_PER_S / (ms / 1e3)
