"""Device peaks: one file per ``device_kind`` under ``benchmark/peaks``
(spaces in the kind become ``_``).  An unknown device is an error."""

import json
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks")


def peak(device_kind: str, key: str) -> float:
    path = os.path.join(_DIR, device_kind.replace(" ", "_") + ".json")
    if not os.path.exists(path):
        raise KeyError("no peaks for device kind %r (%s): add the file "
                       "with its source, do not default"
                       % (device_kind, path))
    with open(path) as f:
        return float(json.load(f)[key])
