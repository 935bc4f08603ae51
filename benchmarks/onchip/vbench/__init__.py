"""The on-chip benchmark's own code: loading cells by name, building the
service under test, the plain references, the counting functions and
the trace reduction. Nothing here touches a device at import."""
