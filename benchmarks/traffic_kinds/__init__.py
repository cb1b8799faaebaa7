"""Traffic generators, one module per kind. A kind gives ``warm(c)`` (set-up:
the cell's own shapes once through the whole path) and ``run(c)`` (the
window), both run inside one client process per connection. ``c`` carries
``client`` (a ``TensorClient``), ``conn``, ``config``, ``traffic``, ``bank``,
``seq`` (next sequence number), ``t0`` and ``seconds``. ``run`` returns
``attempted``, ``acked``, ``failed``, ``t_first_send``, ``t_last_reply`` and,
where calls are timed one by one, ``latency_ns``."""
