"""The chip benchmark's own code: cell lookup, traffic generation, the
plain reference's shared parts, trace reduction and the work formulas.
It imports the program under test (``repro``) only to drive it.  Each
configuration's model reference is a module of its own, named by path
in the configuration file (``refs/``)."""
