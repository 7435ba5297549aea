"""The benchmark's general code: cell lookup, device, timing, trace reduction."""
