"""The state write's share of the HBM roofline, in % (device_trace): the
least time the chip needs to move the states written in the traced span
(each byte read from where it landed and written into its slot) over the
program's own device time in that span. The program is found among the
trace's device programs by the name the configuration states
(``state_program``). The traced span's bytes are the handler's units
counted in it (``trace.payload_bytes``: pages and states together) times
the states' share of the bytes the cache wrote over the whole window
(``kv_state_bytes_written`` against ``kv_pages_written`` page-layers of the
configuration's page): the span's own share is not counted apart. Nothing
to read where the program is not among the traced ones, or the cache wrote
no state."""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "kv_handoff_kda_mla_hybrid.json")


def state_bytes_traced(run, page_bytes: int):
    """The state bytes of the traced span, or None."""
    c = run["counters"]
    states = c.get("kv_state_bytes_written", 0)
    total = states + c.get("kv_pages_written", 0) * page_bytes
    if not states or not run["trace"].get("payload_bytes"):
        return None
    return run["trace"]["payload_bytes"] * states / total


def read(run):
    trace = run.get("trace")
    if not trace or not run.get("peaks"):
        return None
    with open(CONFIG) as f:
        config = json.load(f)
    nbytes = state_bytes_traced(run, int(config["page"]["bytes"]))
    took = None
    for op, seconds in trace.get("device_ops") or ():
        if op == config["state_program"] and seconds > 0:
            took = seconds
    if nbytes is None or took is None:
        return None
    return 100.0 * 2.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / took
