"""Traffic generators, one module a kind. A mix is a data file of parameters
under ``benchmark/workloads/`` that names its kind."""
