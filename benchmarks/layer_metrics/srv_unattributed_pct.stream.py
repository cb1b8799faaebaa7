"""Share of the server calls' time that no top-level stage covers, in %
(program_counter): 100 x (1 - (``srv_recv`` + ``srv_handler`` + ``srv_send``
busy ns) / ``lens_srv_call_busy_ns``). What is left is the call path's own:
taking the buffer, the call's deserializer, the generator's steps, trailers."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_call_busy_ns"):
        return None
    staged = sum(c.get(f"lens_{hop}_busy_ns", 0)
                 for hop in ("srv_recv", "srv_handler", "srv_send"))
    return 100.0 * (1.0 - staged / c["lens_srv_call_busy_ns"])
