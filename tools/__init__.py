# repo tooling (trace_export, trace_scopes) — importable from tests
