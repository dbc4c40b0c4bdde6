"""The yardstick: trace -> numbers, the table of peaks, and the functions
that compute a step's operations and bytes from its shapes."""
