"""Share of the chip-phase slots of the sharded chunks in which a chip
waits for the slowest chip: each chunk's dispatch runs every chip's
lanes until the chip whose lanes need the most phases is done, so
100 x (1 - sum over chunks and chips of each chip's own max phase delta
/ sum over chunks of chips x the largest of them), over the ``"chunk"``
events that ran on more than one device (``chip_phases``). None where
the program emits no ``chip_phases`` or no sharded chunk ran a phase."""


def read(run):
    used = slots = 0
    for u in run.units:
        for c in u.chunks:
            if c.get("devices", 1) < 2 or "chip_phases" not in c:
                continue
            per_chip = c["chip_phases"]
            used += sum(per_chip)
            slots += len(per_chip) * max(per_chip)
    return 100.0 * (1.0 - used / slots) if slots else None
