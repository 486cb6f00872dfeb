"""The FL-round benchmark's harness: cells from data files, the timed
window, the reduction of a device trace, and the correctness check."""
