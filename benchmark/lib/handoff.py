"""The one channel between a driver and a workflow file that the launcher
loads by path (the launcher gives a workflow file nothing but ``load`` and
``main``).  The driver fills ``SLOT`` before it calls the launcher; the
workflow file reads it on the launcher's thread."""

SLOT = {}
