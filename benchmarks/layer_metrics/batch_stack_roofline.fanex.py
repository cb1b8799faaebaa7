"""The stack program's share of the HBM roofline, in % (device_trace): the
least time the chip needs to gather the rows stacked in the traced span (each
payload byte read once and written once) over the stack program's own device
time in that span. ``batch_stack_roofline.fanin``'s formula under
``fanex4m_c8``: the program is found among the trace's device programs by
the name the cell's configuration states (``stack_program``). The rows
counted are those the handler gave the batcher in the span (its
``counts()``): request rows, pad rows not. The program writes ``max_rows``
rows whatever the occupancy (pad rows are read and written too) and that
work is left out, so the share cannot pass 100 and falls with the occupancy.
Nothing to read where the program is not among the traced ones."""

import json
import os

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "configs", "tensor_fanin_exchange_4m.json")


def least_seconds(stacked_bytes: int, hbm_bytes_per_s: float) -> float:
    """Every gathered byte crosses HBM twice: read from where it landed,
    written into the batch."""
    return 2.0 * stacked_bytes / hbm_bytes_per_s


def program_seconds(device_ops, name: str):
    """Device time of the program called ``name`` among ``[[name, seconds],
    ...]`` (``trace_reduce`` keeps the ten longest); None where absent."""
    for op, seconds in device_ops or ():
        if op == name and seconds > 0:
            return seconds
    return None


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("payload_bytes") or not run.get("peaks"):
        return None
    with open(CONFIG) as f:
        name = json.load(f)["stack_program"]
    took = program_seconds(trace.get("device_ops"), name)
    if took is None:
        return None
    return 100.0 * least_seconds(trace["payload_bytes"],
                                 run["peaks"]["hbm_bytes_per_s"]) / took
