"""Tools run by hand on the chip while a cell is defined: the sweep that
finds a serving cell's knee, and a look at a trace's planes and lines."""
