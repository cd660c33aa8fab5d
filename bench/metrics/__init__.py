"""One reader per metric: ``bench/metrics/<name>.py`` reads the metric
``<name>`` and every ``<name>.<split>`` of it (``.solve``, ``.batch``: the
same quantity, split by the end-to-end metric it moves). ``read(run)``
takes a ``bench.drive.Run`` and returns a number, or None where the run
holds nothing to read; the harness then leaves the metric out."""
