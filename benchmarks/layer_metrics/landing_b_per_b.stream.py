"""Bytes the landing moves per payload byte (program_counter): the server
ledger's ``dma_h2d`` + ``dma_d2d`` over the window. 3.0 today (one h2d, the
ring place, the view); 1.0 is ROADMAP A3's aim."""


def read(run):
    if not run["payload_bytes"]:
        return None
    led = run["server_ledger"]
    return (led.get("dma_h2d", 0) + led.get("dma_d2d", 0)) / run["payload_bytes"]
