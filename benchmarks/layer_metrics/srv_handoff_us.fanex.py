"""What the stream's handler does with one request besides decoding it, in
us per request (program_counter): (``lens_srv_handler_busy_ns`` -
``lens_decode_busy_ns``) / ``lens_srv_handler_ops``.

``srv_handoff_us.fanin``'s formula under ``fanex4m_c8``, where
``srv_handler`` ends at the yield: the handler takes the request's leases
(``DeviceRequests.take_leases``), gives the row to the batcher
(``FanInBatcher.submit``), yields the row's future, the server queues it
behind the stream's earlier replies (``_DeferredReplies.push``), and the
thread asks for the next request. A reply written meanwhile is another
thread's (``srv_send_us``); one that was already resolved at the yield is
written here and taken out of the stage."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_handler_ops"):
        return None
    own = (c.get("lens_srv_handler_busy_ns", 0)
           - c.get("lens_decode_busy_ns", 0))
    return own / c["lens_srv_handler_ops"] / 1e3
