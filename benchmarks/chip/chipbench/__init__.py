"""The on-chip benchmark's own code: the device check, traffic generation,
serving window, trace reduction, work counts, and the output check.  Only
``session`` and ``check`` import the serving program; nothing here touches
a device while it is imported."""
