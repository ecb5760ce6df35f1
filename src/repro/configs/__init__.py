"""Deployment configurations, one module each, imported by module path
(`repro.configs.dlrm_production`)."""
