"""Bytes moved on the device to form batches, per payload byte
(program_counter): the server ledger's ``dma_d2d`` over the window / the
payload acknowledged. 1.0 is the floor of a stacked batch (each payload byte
gathered once; pad rows are not billed), 0 would be a batch fused into its
consumer. A run whose batches never stacked on the device (no ``batch_stack``
op: a tree without the hop) gives nothing to read."""


def read(run):
    if (not run["payload_bytes"]
            or not run["counters"].get("lens_batch_stack_ops")):
        return None
    return run["server_ledger"].get("dma_d2d", 0) / run["payload_bytes"]
