"""Put before site-packages for the daemons of a configuration whose
`p2p_codec` is `zlib`: `net/p2p.py` then takes its zlib fallback, as on a
host without the zstandard module (PERF.md, Open question 1a)."""
raise ImportError("zstandard is hidden: this configuration's p2p codec is zlib")
