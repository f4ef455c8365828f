"""One reader per metric: ``<metric>.py`` has ``read(ctx)`` returning the
metric's value, or None where the run holds nothing to read (the harness
then leaves the metric out of the result). ``ctx``, a namespace that
``harness.run_cell`` fills, carries ``setup_s``, the set-up ``spans``
(seconds by name), the ``window`` (``drivers.Window``), ``trace``
(``devtrace.reduce`` of the traced window, or None), ``kernel_calls``
(``work.kernel_calls``), ``kernel_counts`` (the simulator's Pallas
call-site counters) and ``peaks`` (``peaks.json``'s entry for the device,
or None off the chip)."""
