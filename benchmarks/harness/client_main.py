"""One client process: one connection, never a jax process.

``run.py`` starts one of these per connection of the cell's traffic mix (eight
threads in one interpreter would measure the client's GIL). The traffic
*kind* named by the mix (``benchmarks/traffic_kinds/<kind>.py``) does the
sending; this file gives it a connected ``TensorClient``, the connection's
bank of payloads and the start time, and reports what it returns.
"""

from __future__ import annotations

import importlib
import json
import sys
import time
import types


def say(tag: str, obj) -> None:
    sys.stdout.write(f"@{tag} {json.dumps(obj)}\n")
    sys.stdout.flush()


def main() -> int:
    spec = json.loads(sys.argv[1])
    from tpurpc.core import _native
    from tpurpc.jaxshim import TensorClient
    from tpurpc.rpc.channel import Channel
    from tpurpc.tpu import ledger
    from tpurpc.utils.config import get_config

    from benchmarks.harness.payloads import Bank

    kind = importlib.import_module(
        f"benchmarks.traffic_kinds.{spec['traffic']['kind']}")
    bank = Bank(spec["seed"], spec["conn"], spec["config"])
    # started beside the server, so that imports and the bank overlap its
    # way to the chip; the port comes when the server is up
    line = sys.stdin.readline()
    if not line.startswith("port "):
        return 2
    with Channel(f"127.0.0.1:{int(line.split()[1])}") as channel:
        c = types.SimpleNamespace(
            client=TensorClient(channel), conn=spec["conn"],
            config=spec["config"], traffic=spec["traffic"],
            seconds=spec["seconds"], bank=bank, seq=0, t0=None)
        kind.warm(c)
        say("ready", {"native": _native.status(),
                      "platform_type": get_config().platform.name,
                      "warmed": c.seq})
        line = sys.stdin.readline()  # "go <t0 on the monotonic clock>"
        if not line.startswith("go "):
            return 2
        c.t0 = float(line.split()[1])
        before = ledger.snapshot()
        result = kind.run(c)
        after = ledger.snapshot()
        result["ledger"] = {k: after[k] - before[k] for k in after
                            if after[k] != before[k]}
        result["late_s"] = result["t_first_send"] - c.t0
        result["bank_copies"] = bank.copies
        say("result", result)
    if "jax" in sys.modules:
        say("fatal", "a client process imported jax: one process per chip "
            "is broken")
        return 4
    return 0


if __name__ == "__main__":
    sys.exit(main())
