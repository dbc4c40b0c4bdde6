"""The harness: manifest lookup, device checks, the closed-loop window,
spans and the result line. It knows no configuration, traffic mix or
per-layer metric by name."""
