"""The benchmark's yardstick: generators (``gen``), the client and its
loops' records (``client``, ``e2e``), the float64 reference and the
comparison that decides ``correct`` (``reference``, ``control``), the
trace reduction (``trace``), work counts and peaks (``work``, ``peaks``),
and one run of one cell (``session``, ``cells``)."""
