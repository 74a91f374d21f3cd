"""The table of peaks, keyed by ``device_kind``.  A device that is not in
the table is an error, never a default."""
import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def lookup(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_TABLE}: add its "
            f"published peaks with their source before measuring on it")
    return table[device_kind]
