"""A percentile over every measured request of the window, on the
client's clock. spec: {"field": "latency" | "late" | "admit",
"percentile": p}. latency = receipt in hand - due; late = sent - due (how
late the generator ran); admit = sendTransaction answered - sent (only
where the door answers before the receipt)."""

from stats import percentile

FIELDS = {
    "latency": lambda r: None if r.done is None else r.done - r.due,
    "late": lambda r: None if r.sent is None else r.sent - r.due,
    "admit": lambda r: (None if r.admitted is None or r.admitted == r.done
                        else r.admitted - r.sent),
}


def read(ev: dict, spec: dict):
    vals = [v for v in map(FIELDS[spec["field"]], ev["requests"])
            if v is not None]
    if not vals:
        return None
    return 1000.0 * percentile(vals, spec["percentile"])
