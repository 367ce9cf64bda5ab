"""Traffic generators: read a traffic mix's data file and a cell's load."""
