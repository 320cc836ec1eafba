"""The benchmark's own code: manifest, traffic, window arithmetic, trace
reduction, cost arithmetic, the plain reference and the comparison."""
