"""The table of published peaks, keyed by JAX's ``device_kind``.  A device
that is not in the table is an error, never a default."""
import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind, table=TABLE):
    rows = json.loads(Path(table).read_text())
    if device_kind not in rows:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"{Path(table).name} has {sorted(rows)}")
    return rows[device_kind]
